"""Prefill+decode vs full-forward consistency: the strongest cache-
semantics test. For each stateful family we (1) run the full sequence
through `train`-mode forward, (2) run prefill on the prefix + decode the
remaining tokens one by one, and assert the per-position logits agree.

Run in f32 policy so precision noise cannot hide indexing bugs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.precision import PrecisionPolicy
from repro.models import api
from repro.runtime import serve_step

POLICY = PrecisionPolicy.uniform("f32")
B = 2


def _f32(cfg):
    import dataclasses
    # MoE: capacity_factor >= num_experts makes capacity = t*top_k, i.e.
    # dropless — required for prefill/forward consistency, since capacity
    # DROPPING depends on total token count t (train t != prefill t).
    # Decode is natively dropless (moe_ffn dropless=True on that path).
    cf = max(cfg.capacity_factor, float(cfg.num_experts or 1))
    return dataclasses.replace(cfg, activation_dtype="float32",
                               capacity_factor=cf)


def _roundtrip(arch: str, s_total: int = 12, s_prefix: int = 7,
               atol: float = 2e-2):
    cfg = _f32(get_smoke(arch))
    key = jax.random.PRNGKey(11)
    params = api.init_params(key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, s_total), 0,
                                cfg.vocab_size)

    batch_full = {"tokens": tokens}
    batch_pre = {"tokens": tokens[:, :s_prefix]}
    n_img = cfg.num_image_tokens if cfg.family == "vlm" else 0
    if cfg.family == "audio":
        frames = jax.random.normal(
            jax.random.PRNGKey(7), (B, cfg.encoder_seq, cfg.d_model))
        batch_full["frames"] = batch_pre["frames"] = frames
    if cfg.family == "vlm":
        img = jax.random.normal(
            jax.random.PRNGKey(8), (B, n_img, cfg.d_model))
        batch_full["image_embeds"] = batch_pre["image_embeds"] = img

    # Reference: full forward logits at every position.
    if cfg.family == "audio":
        from repro.models import encdec as E
        ref_logits, _, _ = E.forward(params, tokens, batch_full["frames"],
                                     cfg, policy=POLICY, mode="train")
    elif cfg.family == "vlm":
        from repro.models import vlm as V
        ref_logits, _, _ = V.forward(params, tokens,
                                     batch_full["image_embeds"], cfg,
                                     policy=POLICY, mode="train")
    else:
        from repro.models import transformer as T
        ref_logits, _, _ = T.forward(params, tokens, cfg, policy=POLICY,
                                     mode="train")

    # Prefill prefix, pad cache to capacity, then decode token by token.
    s_ctx = api.context_len(cfg, s_total)
    prefill = serve_step.make_prefill(cfg, POLICY, s_ctx=s_ctx)
    decode = serve_step.make_decode(cfg, POLICY)
    logits_p, cache = prefill(params, batch_pre)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, -1], np.float32),
        np.asarray(ref_logits[:, n_img + s_prefix - 1], np.float32),
        rtol=0, atol=atol, err_msg=f"{arch}: prefill last-logit mismatch")

    for t in range(s_prefix, s_total):
        tok = tokens[:, t:t + 1]
        pos = jnp.full((B,), n_img + t, jnp.int32)   # per-row positions
        logits_d, cache = decode(params, cache, tok, pos)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(ref_logits[:, n_img + t], np.float32),
            rtol=0, atol=atol,
            err_msg=f"{arch}: decode@{t} logits diverge from forward")


# One test per stateful family (covers: global attn GQA, local ring-buffer
# attn, 5:1 mixed local/global, moe+SWA, mamba2+shared-attn hybrid, rwkv6
# recurrence, enc-dec cross-attn, vlm image-prefix offsets).

@pytest.mark.parametrize("arch", [
    "starcoder2-15b",   # pure global GQA
    "gemma3-1b",        # 5:1 local(window ring buffer):global
    "mixtral-8x7b",     # MoE + sliding-window attention
    "dbrx-132b",        # MoE, global attn
    "zamba2-7b",        # mamba2 + shared_attn hybrid
    "rwkv6-7b",         # rwkv6 recurrence
    "whisper-medium",   # enc-dec with cross-attention cache
    "internvl2-76b",    # vlm image-prefix position offsets
    "deepseek-v2-lite",  # latent attention, shared experts, expert share
])
def test_prefill_decode_matches_forward(arch):
    _roundtrip(arch)


def test_window_ring_buffer_long_decode():
    """Decode far past the window: ring buffer must keep exactly the last
    `window` tokens (gemma3-style local layers)."""
    cfg = _f32(get_smoke("gemma3-1b"))
    assert cfg.window is not None
    s_total = cfg.window + 9            # decode well past one window
    _roundtrip("gemma3-1b", s_total=s_total, s_prefix=5)


def test_prefill_longer_than_window():
    """Prefill itself longer than the window: cache must hold the LAST
    window tokens in ring order."""
    cfg = _f32(get_smoke("mixtral-8x7b"))
    _roundtrip("mixtral-8x7b", s_total=cfg.window + 8,
               s_prefix=cfg.window + 3)


# ============================================================ serve engine
# Continuous-batching engine parity: slots admitted at DIFFERENT ticks
# (per-slot position vectors) must reproduce the batch-of-one outputs
# token for token. This is the oracle for the shared-scalar-pos bug.

from repro.launch.serve import Request, ServeEngine  # noqa: E402
from repro.models.attention import AttnCache  # noqa: E402

MAX_CTX = 32


def _engine(cfg, params, batch_size):
    eng = ServeEngine(cfg, batch_size=batch_size, max_ctx=MAX_CTX,
                      policy=POLICY)
    eng.load(params)
    return eng


@pytest.mark.parametrize("arch", [
    "starcoder2-15b",   # pure global GQA
    "gemma3-1b",        # 5:1 local(window ring buffer):global
    "mixtral-8x7b",     # MoE + sliding-window attention
    "dbrx-132b",        # MoE, global attn
    "zamba2-7b",        # mamba2 + shared_attn hybrid
    "rwkv6-7b",         # rwkv6 recurrence
    "whisper-medium",   # enc-dec with cross-attention cache
    "internvl2-76b",    # vlm image-prefix position offsets
    "deepseek-v2-lite",  # latent attention, shared experts, expert share
])
def test_staggered_admission_matches_single(arch):
    """4 requests with different prompt lengths and token budgets on a
    2-slot engine: admissions land at different ticks, so every slot
    decodes at its own position. Outputs must equal serving each request
    alone (greedy, same params)."""
    cfg = _f32(get_smoke(arch))
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(17)
    prompts = [rng.integers(2, cfg.vocab_size, 4 + (i % 3)).astype(np.int32)
               for i in range(4)]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4 + (i % 3))
            for i, p in enumerate(prompts)]

    eng = _engine(cfg, params, batch_size=2)
    stats = eng.run(reqs)
    assert all(r.done for r in reqs)
    # accounting: every generated token (prefill-sampled first token and
    # the final token of every request) is counted exactly once
    assert stats["tokens"] == sum(len(r.out_tokens) for r in reqs)
    assert all(r.latency_s is not None and r.latency_s >= 0 for r in reqs)

    for i, p in enumerate(prompts):
        ref = Request(rid=100 + i, prompt=p,
                      max_new_tokens=reqs[i].max_new_tokens)
        _engine(cfg, params, batch_size=1).run([ref])
        assert reqs[i].out_tokens == ref.out_tokens, (
            f"{arch}: staggered req {i} diverged from batch-of-one: "
            f"{reqs[i].out_tokens} vs {ref.out_tokens}")


def test_run_stats_are_per_run():
    """A second run() on the same engine must report only that run's
    tokens/ticks, not the engine-lifetime counters."""
    cfg = _f32(get_smoke("starcoder2-15b"))
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(31)
    eng = _engine(cfg, params, batch_size=1)
    for rid in range(2):
        req = Request(rid=rid,
                      prompt=rng.integers(2, cfg.vocab_size, 4).astype(np.int32),
                      max_new_tokens=3)
        stats = eng.run([req])
        assert stats["tokens"] == len(req.out_tokens), (rid, stats)


def test_prefill_eos_completes_request():
    """An EOS sampled directly from prefill must mark the request done
    without it ever occupying a decode slot."""
    cfg = _f32(get_smoke("starcoder2-15b"))
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(23)
    prompt = rng.integers(2, cfg.vocab_size, 5).astype(np.int32)
    # discover what prefill greedily samples, then serve with THAT as eos
    probe = Request(rid=0, prompt=prompt, max_new_tokens=8)
    _engine(cfg, params, batch_size=1).run([probe])
    first = probe.out_tokens[0]

    eng = ServeEngine(cfg, batch_size=1, max_ctx=MAX_CTX, policy=POLICY,
                      eos_id=first)
    eng.load(params)
    req = Request(rid=1, prompt=prompt, max_new_tokens=8)
    eng.run([req])
    assert req.done and req.out_tokens == [first]
    assert all(r is None for r in eng.slot_req)  # slot never consumed
    assert not bool(np.asarray(eng.active).any())


def test_pad_cache_and_slot_splice():
    """pad_cache grows every growable attention cache to capacity (ring
    buffers stay window-sized) and admit() splices a single-request
    prefill into exactly its slot, leaving other rows untouched."""
    cfg = _f32(get_smoke("gemma3-1b"))
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(29)
    prompt = rng.integers(2, cfg.vocab_size, 6).astype(np.int32)

    # --- pad_cache shape/content contract
    logits1, raw = api.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                               cfg, policy=POLICY)
    padded = serve_step.pad_cache(raw, cfg, MAX_CTX)
    for i, seg in enumerate(cfg.segments):
        for j, kind in enumerate(seg.pattern):
            c_raw = raw[f"seg{i}"][f"pos{j}"]
            c_pad = padded[f"seg{i}"][f"pos{j}"]
            if not isinstance(c_raw, AttnCache):
                continue
            if kind == "attn":
                assert c_pad.k.shape[2] == MAX_CTX
            elif kind == "attn_local":
                assert c_pad.k.shape[2] == min(MAX_CTX, cfg.window)
            s_raw = c_raw.k.shape[2]
            np.testing.assert_array_equal(
                np.asarray(c_pad.k[:, :, :s_raw], np.float32),
                np.asarray(c_raw.k, np.float32))
            assert not np.asarray(c_pad.k[:, :, s_raw:], np.float32).any()

    # --- per-slot splice: admit into slot 1 of a 3-slot engine
    eng = _engine(cfg, params, batch_size=3)
    eng.slot_req[0] = Request(rid=99, prompt=prompt)  # occupy slot 0
    assert eng.admit(Request(rid=0, prompt=prompt, max_new_tokens=4))
    assert eng.slot_req[1] is not None and eng.slot_req[1].rid == 0

    def rows(leaf_batch, leaf_one):
        if not isinstance(leaf_one, AttnCache):
            return
        # stacked leaves are (count, B, S, Kv, hd)
        np.testing.assert_array_equal(
            np.asarray(leaf_batch.k[:, 1], np.float32),
            np.asarray(leaf_one.k[:, 0], np.float32))
        assert not np.asarray(leaf_batch.k[:, 2], np.float32).any()

    for i, seg in enumerate(cfg.segments):
        for j in range(len(seg.pattern)):
            rows(eng.cache[f"seg{i}"][f"pos{j}"],
                 serve_step.pad_cache(raw, cfg, MAX_CTX)[f"seg{i}"][f"pos{j}"])


# ======================================================= replica pool parity
# Token outputs must be replica-count independent: the pool only ROUTES;
# every engine runs the same greedy decode on the same params, and engine
# outputs are batch-composition independent (staggered-admission test
# above). 1 replica vs round-robined across 3 must match token for token.

from repro.serve.pool import ReplicaPool  # noqa: E402


def test_pool_replica_count_is_token_invariant():
    cfg = _f32(get_smoke("gemma3-1b"))
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(41)
    prompts = [rng.integers(2, cfg.vocab_size, 4 + (i % 3)).astype(np.int32)
               for i in range(6)]
    budgets = [3 + (i % 3) for i in range(6)]

    def stream():
        return [Request(rid=i, prompt=p, max_new_tokens=b)
                for i, (p, b) in enumerate(zip(prompts, budgets))]

    one = stream()
    ReplicaPool(cfg, params, replicas=1, batch_size=2, max_ctx=MAX_CTX,
                policy=POLICY).run(one)

    three = stream()
    pool3 = ReplicaPool(cfg, params, replicas=3, batch_size=2,
                        max_ctx=MAX_CTX, policy=POLICY,
                        routing="round_robin")
    stats = pool3.run(three)
    assert stats["replicas"] == 3
    # the spread is real: every replica decoded some of the stream
    assert all(r.engine.tokens_generated > 0 for r in pool3.replicas)

    for a, b in zip(one, three):
        assert a.out_tokens == b.out_tokens, (
            f"req {a.rid} diverged across replica counts: "
            f"{a.out_tokens} vs {b.out_tokens}")
