"""Latent attention (DeepSeek-V2 MLA) on the smoke preset, CPU, seeded
random weights, f32 throughout:

* prefill, then the cached absorbed decode, against the plain float32
  reference's full-forward logits (``benchmarks/chip/references/mla.py``);
* the absorbed decode against the expanded attention on the same cache;
* the reference against the transformers ``DeepseekV2ForCausalLM`` with
  the same weights, the mscale**2 softmax factor applied on both sides;
* ``ServeEngine`` token for token against an unbatched greedy decode.
"""

import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.precision import PrecisionPolicy
from repro.launch.serve import Request, ServeEngine
from repro.models import api
from repro.models import mla as MLA
from repro.runtime import serve_step

POLICY = PrecisionPolicy.uniform("f32")
ROOT = Path(__file__).resolve().parents[1]


def load_reference():
    path = ROOT / "benchmarks" / "chip" / "references" / "mla.py"
    spec = importlib.util.spec_from_file_location("reference_mla", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def smoke_f32():
    return dataclasses.replace(get_smoke("deepseek-v2-lite"),
                               activation_dtype="float32")


def hf_keys(cfg) -> dict:
    """The published config.json keys of a ModelConfig: what the
    reference reads."""
    y = cfg.yarn
    dense = cfg.segments[0].count
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.head_dim, "qk_rope_head_dim": cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "rope_scaling": {"factor": y.factor, "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim,
                         "original_max_position_embeddings":
                             y.original_max_pos,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "type": "yarn"},
        "first_k_dense_replace": dense,
        "num_hidden_layers": cfg.num_layers,
        "intermediate_size": cfg.d_ff, "moe_intermediate_size": cfg.moe_d_ff,
        "n_shared_experts": cfg.shared_d_ff // cfg.moe_d_ff,
        "n_routed_experts": cfg.router_experts,
        "num_local_experts": cfg.num_experts,
        "first_local_expert": cfg.first_expert,
        "num_experts_per_tok": cfg.top_k, "routed_scaling_factor": 1.0,
        "vocab_size": cfg.vocab_size}


@pytest.fixture(scope="module")
def model():
    cfg = smoke_f32()
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def test_prefill_then_absorbed_decode_matches_reference(model):
    """Both sides compute in f32 (the reference at HIGHEST, the program
    through its own einsum order and a flash-over-KV online softmax), so
    they differ by summation order alone: 1e-4 on logits of order 1 is
    about 100 float32 roundings, and a missing term of the mathematics
    (the rope, the mscale**2 factor, one expert) moves them by 1e-2 or
    more."""
    cfg, params, tokens = model
    ref = load_reference()
    keys = hf_keys(cfg)
    s_pre, s_total = 12, tokens.shape[1]
    want = np.stack([np.asarray(ref.logits(params, t, keys)) for t in tokens])
    prefill = serve_step.make_prefill(cfg, POLICY, s_ctx=32)
    decode = serve_step.make_decode(cfg, POLICY)
    logits, cache = prefill(params, {"tokens": tokens[:, :s_pre]})
    assert isinstance(cache["seg1"]["pos0"], MLA.LatentCache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, s_pre - 1],
                               rtol=0, atol=1e-4)
    for t in range(s_pre, s_total):
        logits, cache = decode(params, cache, tokens[:, t:t + 1],
                               jnp.full((2,), t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, t],
                                   rtol=0, atol=1e-4, err_msg=f"pos {t}")


def test_decode_cache_holds_only_the_latent(model):
    cfg, _, _ = model
    cache = api.init_cache(cfg, 3, 16, jnp.float32)
    leaf = cache["seg1"]["pos0"]
    assert leaf.latent.shape == (2, 3, 16, cfg.kv_lora_rank)
    assert leaf.k_rope.shape == (2, 3, 16, cfg.qk_rope_dim)


def test_absorbed_decode_equals_expanded_attention(model):
    """The attention output at position S from the absorbed decode (the
    cache of the first S tokens plus the row it writes) equals the
    expanded causal attention over all S + 1 tokens at that position.
    Same f32 arithmetic regrouped (W_UK and W_UV moved across the
    softmax), so 1e-5 on outputs of order 1."""
    cfg, params, _ = model
    p = jax.tree.map(lambda w: w[0], params["seg1"]["pos0"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
    full, _ = MLA.mla_attention(p, x, cfg=cfg, mode="train", policy="f32")
    _, cache = MLA.mla_attention(p, x[:, :8], cfg=cfg, mode="prefill",
                                 policy="f32")
    # a stack of one layer, as the segment scan carries it
    cache = MLA.LatentCache(*(jnp.pad(c, ((0, 0), (0, 8), (0, 0)))[None]
                              for c in cache))
    out, new = MLA.mla_attention(p, x[:, 8:], cfg=cfg, mode="decode",
                                 policy="f32", cache=cache,
                                 pos=jnp.full((2,), 8, jnp.int32),
                                 layer=jnp.int32(0))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, 8]),
                               rtol=0, atol=1e-5)
    # the decode wrote its own row and nothing else
    np.testing.assert_array_equal(np.asarray(new.latent[0, :, :8]),
                                  np.asarray(cache.latent[0, :, :8]))
    assert np.abs(np.asarray(new.latent[0, :, 8])).max() > 0


def test_yarn_scale_is_deepseeks():
    """The softmax scale is 192**-0.5 times mscale(40, 0.707)**2, about
    1.590, at the published widths."""
    from repro.configs import get_config
    cfg = get_config("deepseek-v2-lite")
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert MLA.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert mscale ** 2 == pytest.approx(1.590, abs=1e-3)


def _hf_model(cfg, params):
    """transformers' DeepseekV2ForCausalLM with the program's weights
    copied in, and the mscale**2 factor put on its softmax scale."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    keys = hf_keys(cfg)
    hf_cfg = tf.DeepseekV2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        intermediate_size=cfg.d_ff, moe_intermediate_size=cfg.moe_d_ff,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads, n_shared_experts=2,
        n_routed_experts=cfg.router_experts, routed_scaling_factor=1.0,
        kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=None,
        qk_rope_head_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        qk_nope_head_dim=cfg.head_dim, topk_method="greedy", n_group=1,
        topk_group=1, num_experts_per_tok=cfg.top_k,
        first_k_dense_replace=keys["first_k_dense_replace"],
        norm_topk_prob=False, max_position_embeddings=256,
        rope_theta=cfg.rope_theta, rope_scaling=dict(keys["rope_scaling"]),
        rms_norm_eps=cfg.norm_eps, tie_word_embeddings=False,
        attention_bias=False, attn_implementation="eager")
    model = tf.DeepseekV2ForCausalLM(hf_cfg).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    sd = {"model.embed_tokens.weight": t(params["embed"]["table"]),
          "lm_head.weight": t(params["unembed"]["table"]),
          "model.norm.weight": t(params["final_norm"]["scale"])}
    dense = keys["first_k_dense_replace"]
    for layer in range(cfg.num_layers):
        seg, i = ("seg0", layer) if layer < dense else ("seg1", layer - dense)
        a = jax.tree.map(lambda w: w[i], params[seg]["pos0"])
        f = jax.tree.map(lambda w: w[i], params[seg]["pos1"])
        pre = f"model.layers.{layer}."
        sd.update({
            pre + "input_layernorm.weight": t(a["norm"]["scale"]),
            pre + "self_attn.q_proj.weight": t(a["wq"]["w"]).T,
            pre + "self_attn.kv_a_proj_with_mqa.weight": t(a["wkv_a"]["w"]).T,
            pre + "self_attn.kv_a_layernorm.weight": t(a["kv_norm"]["scale"]),
            pre + "self_attn.kv_b_proj.weight": t(a["wkv_b"]["w"]).T,
            pre + "self_attn.o_proj.weight": t(a["wo"]["w"]).T,
            pre + "post_attention_layernorm.weight": t(f["norm"]["scale"])})

        def swiglu(prefix, w, e=None):
            get = (lambda n: w[n]["w"]) if e is None else \
                (lambda n: w[n]["w"][e])
            sd.update({prefix + "gate_proj.weight": t(get("wg")).T,
                       prefix + "up_proj.weight": t(get("wi")).T,
                       prefix + "down_proj.weight": t(get("wo")).T})

        if layer < dense:
            swiglu(pre + "mlp.", f)
        else:
            sd[pre + "mlp.gate.weight"] = t(f["router"]["w"]).T
            swiglu(pre + "mlp.shared_experts.", f["shared"])
            for e in range(cfg.num_experts):
                swiglu(pre + f"mlp.experts.{e}.", f, e)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and not [k for k in missing if "rotary" not in k]
    scale = MLA.softmax_scale(cfg) / (cfg.head_dim + cfg.qk_rope_dim) ** -0.5
    for layer in model.model.layers:
        layer.self_attn.scaling *= scale
    return model


def test_reference_matches_transformers_deepseek_v2():
    """Uncut (all 8 experts held) so that the two compute the same
    layer; both in f32, so 1e-4 on logits of order 1 is summation
    order."""
    cfg = dataclasses.replace(smoke_f32(), num_experts=8)
    params = api.init_params(jax.random.PRNGKey(4), cfg)
    model = _hf_model(cfg, params)
    import torch
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (24,), 0,
                                           cfg.vocab_size))
    with torch.no_grad():
        want = model(torch.tensor(tokens[None])).logits[0].numpy()
    got = np.asarray(load_reference().logits(params, jnp.asarray(tokens),
                                             hf_keys(cfg)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)


def _greedy_alone(cfg, params, prompt, n):
    """One request, batch 1, outside the engine: prefill, then decode
    one token at a time through api.decode."""
    logits, cache = api.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                                cfg, policy=POLICY)
    cache = serve_step.pad_cache(cache, cfg, 32)
    out = [int(jnp.argmax(logits[0, -1]))]
    for k in range(n - 1):
        logits, cache = api.decode(
            params, cache, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.asarray([len(prompt) + k], jnp.int32), cfg, policy=POLICY)
        out.append(int(jnp.argmax(logits[0, 0])))
    return out


def test_engine_serves_token_exactly_against_unbatched_decode(model):
    cfg, params, _ = model
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, 3 + 2 * i)
                    .astype(np.int32), max_new_tokens=5 + i)
            for i in range(4)]
    eng = ServeEngine(cfg, batch_size=2, max_ctx=32, policy=POLICY, eos_id=-1)
    eng.load(params)
    eng.run(reqs)
    for r in reqs:
        assert r.out_tokens == _greedy_alone(cfg, params, r.prompt,
                                             r.max_new_tokens), r.rid
