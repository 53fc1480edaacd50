"""Bring-up check: the system's main path on one TPU chip, end to end.

Drives gemma3-1b through the entry points a user calls —
``repro.launch.serve.ServeEngine`` / ``Request`` for serving and
``repro.launch.train.TrainLoop`` for training — with weights made from
``--seed``.  Phases:

  device        the first JAX device must be a TPU; there is no CPU
                fallback, so a run that cannot see the chip fails here.
  serve_xla     the full published config (26 layers) on the default
                route (XLA everywhere), dense KV cache: 8 requests of
                256 prompt tokens, staggered so admission interleaves
                with decode, 32 new tokens each (termination by budget
                only).
  serve_pallas  the same requests on the Pallas route (tiled GEMM,
                fused flash attention, paged-KV decode kernel), compiled
                (not interpreted); its prefill and decode logits are
                compared with serve_xla's.
  train         3 AdamW steps at published widths, depth cut to the
                first 6-layer period (5 local + 1 global), on the Pallas
                route so the fused backward kernels run.

``--chips 4`` runs only the four-chip phase: the train phase's steps
under ``MeshSpec(dp=2, tp=2)`` against the same steps with no mesh, from
the same seed in this one process.

Timings printed per phase are informational wall-clock readings of one
run: compile (set-up) apart from the timed part.  The last line of
standard output is one JSON object naming the device.

Run from the repository root, on a machine whose JAX sees a TPU:

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four-chip sharded train phase
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

_SRC = Path(__file__).resolve().parent / "src"
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import Segment, execution_policy_for  # noqa: E402
from repro.core import ops  # noqa: E402
from repro.data.pipeline import DataConfig  # noqa: E402
from repro.launch.serve import Request, ServeEngine  # noqa: E402
from repro.launch.train import TrainLoop  # noqa: E402
from repro.models import api  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.runtime import serve_step  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "gemma3-1b"
PALLAS_BACKENDS = {"gemm": "pallas", "attention": "pallas_fused"}

# Relative L2 distance allowed between the two serve routes' logits.
# Both run bf16-input MXU passes with f32 accumulation over a bf16
# residual stream; with every policy at f32 the routes agree to ~1e-6,
# so what separates them under bf16 is only where roundings fall.  One
# bf16 rounding is 2^-8 relative; compounding over 26 layers like a
# random walk gives about sqrt(26) * 2^-8 ~ 2e-2 (a 26-layer CPU run at
# small widths measured 1.8e-2 prefill, 2.2e-2 decode).  The limit
# leaves 2.5x over that; a real fault (mask, layout, page walk) moves
# logits by O(1).
LOGIT_RTOL = 5e-2
# Sharded and unsharded training run the same kernels on slices; only
# reductions (row-parallel GEMM psums, the loss mean over the batch)
# reorder f32 sums.  Largest |loss difference| over the steps:
LOSS_ATOL = 1e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Shapes of one smoke run (the defaults are the chip run's)."""

    batch: int = 8             # serve slots
    max_ctx: int = 2048
    prompt_len: int = 256
    new_tokens: int = 32
    requests: int = 8
    stagger: int = 2           # requests submitted per engine step
    train_batch: int = 2
    train_seq: int = 512
    train_steps: int = 3


@dataclasses.dataclass
class ServeRun:
    route: str
    compile_s: float
    run_s: float
    tokens: int
    outputs: list
    prefill_logits: np.ndarray     # (V,) last prompt position
    decode_logits: np.ndarray      # (V,) one decode tick after the prompt
    prefill_hlo_has_kernel: bool


@dataclasses.dataclass
class TrainRun:
    compile_s: float
    run_s: float
    tokens: int
    losses: list
    params: object


def check_device() -> dict:
    """The device phase: a TPU or a non-zero exit, never a fallback."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (first device is "
                         f"{dev.platform!r}); there is no CPU fallback")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def serve_policy(cfg, backends=None, kv_layout: str = "dense"):
    attn_caps = (("decode", "paged_decode") if kv_layout == "paged"
                 else ("decode",))
    return execution_policy_for(cfg, backends=backends,
                                require={"attention": attn_caps})


def make_prompts(cfg, sizes: Sizes, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return list(rng.integers(2, cfg.vocab_size,
                             (sizes.requests, sizes.prompt_len),
                             dtype=np.int32))


def _run_staggered(engine: ServeEngine, requests: list, stagger: int):
    pending = list(requests)
    while pending or not engine.idle:
        for _ in range(min(stagger, len(pending))):
            engine.submit(pending.pop(0))
        engine.step()


def serve_phase(cfg, params, prompts: list, policy, *, sizes: Sizes,
                route: str, kv_layout: str, probe_token: int) -> ServeRun:
    """Serve ``prompts`` through one ServeEngine; then probe its prefill
    logits for ``prompts[0]`` and the decode logits of ``probe_token``
    fed right after that prompt."""
    engine = ServeEngine(cfg, batch_size=sizes.batch, max_ctx=sizes.max_ctx,
                         policy=policy, eos_id=-1, kv_layout=kv_layout)
    engine.load(params)

    t0 = time.perf_counter()
    # Set-up: one request compiles the prefill (one prompt length) and
    # the engine tick, so the timed run below compiles nothing.
    engine.run([Request(rid=-1, prompt=prompts[0], max_new_tokens=2)])
    jax.block_until_ready(engine.cache)
    compile_s = time.perf_counter() - t0

    requests = [Request(rid=i, prompt=p, max_new_tokens=sizes.new_tokens)
                for i, p in enumerate(prompts)]
    tokens0 = engine.tokens_generated
    t0 = time.perf_counter()
    _run_staggered(engine, requests, sizes.stagger)
    run_s = time.perf_counter() - t0
    tokens = engine.tokens_generated - tokens0
    for r in requests:
        if not r.done or len(r.out_tokens) != sizes.new_tokens:
            raise AssertionError(
                f"{route}: request {r.rid} returned {len(r.out_tokens)} "
                f"tokens, want {sizes.new_tokens}")

    # The engine's own compiled prefill program, on the first prompt.
    batch = {"tokens": jnp.asarray(prompts[0])[None]}
    prefill_logits, _ = engine._prefill(params, batch)
    hlo = engine._prefill.lower(params, batch).as_text()

    # One decode step on the engine's cache after the first prompt was
    # admitted (dense rows or pages, as the route keeps them), feeding
    # the same token on both routes.
    probe = Request(rid=-2, prompt=prompts[0],
                    max_new_tokens=sizes.new_tokens)
    if not engine.admit(probe):
        raise AssertionError(f"{route}: no free slot for the probe")
    slot = engine.slot_req.index(probe)
    decode = jax.jit(serve_step.make_decode(cfg, policy))
    toks = jnp.full((sizes.batch, 1), probe_token, jnp.int32)
    decode_logits, _ = decode(params, engine.cache, toks, engine.pos)
    engine.cancel(probe.rid)

    out = ServeRun(
        route=route, compile_s=compile_s, run_s=run_s,
        tokens=tokens,
        outputs=[list(r.out_tokens) for r in requests],
        prefill_logits=np.asarray(prefill_logits, np.float32)[0, -1],
        decode_logits=np.asarray(decode_logits, np.float32)[slot, 0],
        prefill_hlo_has_kernel="tpu_custom_call" in hlo)
    for name in ("prefill_logits", "decode_logits"):
        if not np.isfinite(getattr(out, name)).all():
            raise AssertionError(f"{route}: non-finite {name}")
    return out


def check_compiled(policy, run: ServeRun) -> None:
    """The Pallas route ran compiled kernels, not the interpreter."""
    for fam in ("mlp", "attention", "logits"):
        if policy.for_(fam).resolved_interpret():
            raise AssertionError(f"{fam} route resolves interpret=True")
    if not run.prefill_hlo_has_kernel:
        raise AssertionError(f"{run.route} prefill lowers no tpu_custom_call")


def rel_l2(a: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def compare_serve(xla: ServeRun, pallas: ServeRun) -> dict:
    """Logit agreement of the Pallas route with the XLA route."""
    deltas = {"prefill": rel_l2(pallas.prefill_logits, xla.prefill_logits),
              "decode": rel_l2(pallas.decode_logits, xla.decode_logits)}
    print(f"serve logits, pallas vs xla (relative L2, limit "
          f"{LOGIT_RTOL}): prefill {deltas['prefill']!r} "
          f"decode {deltas['decode']!r}", flush=True)
    for name, d in deltas.items():
        if not d <= LOGIT_RTOL:
            raise AssertionError(
                f"{name} logits differ by {d!r} > {LOGIT_RTOL}")
    return deltas


def train_config(cfg):
    """Published widths, depth cut to the first period of the pattern."""
    period = cfg.segments[0].pattern
    mixers = sum(k.startswith("attn") for k in period)
    return dataclasses.replace(cfg, segments=(Segment(period, 1),),
                               num_layers=mixers)


def train_phase(cfg, *, sizes: Sizes, seed: int, mesh=None) -> TrainRun:
    """``sizes.train_steps`` TrainLoop steps on the Pallas route."""
    policy = execution_policy_for(
        cfg, backends=PALLAS_BACKENDS,
        require={fam: ("vjp",) for fam in ops.families()}, mesh=mesh)
    data_cfg = DataConfig(global_batch=sizes.train_batch,
                          seq_len=sizes.train_seq,
                          vocab_size=cfg.vocab_size, seed=seed)
    # Full learning rate from the first update, so 3 steps can show it.
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1,
                                total_steps=sizes.train_steps)
    loop = TrainLoop(cfg, policy=policy, opt_cfg=opt_cfg,
                     data_cfg=data_cfg, mesh=mesh)

    t0 = time.perf_counter()
    # Set-up: two steps compile the train step for both of its inputs —
    # freshly initialised parameters, then the step's own outputs (which
    # a mesh gives a sharding of their own).  The run below starts again
    # from the same seed.
    loop.run(2, seed=seed, log_every=0)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    params, _, losses = loop.run(sizes.train_steps, seed=seed, log_every=0)
    jax.block_until_ready(params)
    run_s = time.perf_counter() - t0
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite train losses {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train loss did not fall: {losses}")
    return TrainRun(compile_s=compile_s, run_s=run_s,
                    tokens=sizes.train_steps * sizes.train_batch
                    * sizes.train_seq, losses=losses, params=params)


def param_devices(params) -> set:
    return {s.device for leaf in jax.tree.leaves(params)
            for s in leaf.addressable_shards}


def compare_sharded(sharded: TrainRun, single: TrainRun, n: int) -> float:
    """Losses agree, and the sharded parameters live on ``n`` devices."""
    diff = max(abs(a - b) for a, b in zip(sharded.losses, single.losses))
    devices = param_devices(sharded.params)
    print(f"sharded vs unsharded losses: {sharded.losses} vs "
          f"{single.losses}; max |diff| {diff!r} (limit {LOSS_ATOL}); "
          f"sharded params on {len(devices)} devices", flush=True)
    if not diff <= LOSS_ATOL:
        raise AssertionError(f"sharded losses differ by {diff!r}")
    if len(devices) != n:
        raise AssertionError(f"sharded params on {len(devices)} devices, "
                             f"want {n}")
    split = any(s.data.shape != leaf.shape
                for leaf in jax.tree.leaves(sharded.params)
                for s in leaf.addressable_shards)
    if not split:
        raise AssertionError("no parameter is split across devices")
    return diff


def _report(phase: str, compile_s: float, run_s: float, tokens: int):
    print(f"[{phase}] compile+setup {compile_s!r} s | run {run_s!r} s | "
          f"{tokens} tokens | {tokens / run_s!r} tokens/s "
          f"(wall clock, informational)", flush=True)


def run_one_chip(sizes: Sizes, seed: int) -> None:
    cfg = get_config(ARCH)
    print(f"[serve] {ARCH}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, batch {sizes.batch}, max_ctx {sizes.max_ctx}, "
          f"{sizes.requests} requests x {sizes.prompt_len} prompt + "
          f"{sizes.new_tokens} new tokens", flush=True)
    params = api.init_params(jax.random.PRNGKey(seed), cfg)
    prompts = make_prompts(cfg, sizes, seed)

    xla = serve_phase(cfg, params, prompts, serve_policy(cfg), sizes=sizes,
                      route="serve_xla", kv_layout="dense",
                      probe_token=int(prompts[1][0]))
    _report("serve_xla", xla.compile_s, xla.run_s, xla.tokens)

    policy = serve_policy(cfg, PALLAS_BACKENDS, kv_layout="paged")
    pallas = serve_phase(cfg, params, prompts, policy, sizes=sizes,
                         route="serve_pallas", kv_layout="paged",
                         probe_token=int(prompts[1][0]))
    _report("serve_pallas", pallas.compile_s, pallas.run_s, pallas.tokens)
    check_compiled(policy, pallas)
    compare_serve(xla, pallas)
    del params, xla, pallas

    tcfg = train_config(cfg)
    period = tcfg.segments[0].pattern
    print(f"[train] depth cut: {cfg.num_layers} -> {tcfg.num_layers} layers "
          f"(one period: {period.count('attn_local')} local + "
          f"{period.count('attn')} global), widths as published; "
          f"batch {sizes.train_batch} x seq {sizes.train_seq}", flush=True)
    run = train_phase(tcfg, sizes=sizes, seed=seed)
    print(f"[train] losses {run.losses}", flush=True)
    _report("train", run.compile_s, run.run_s, run.tokens)


def run_four_chips(sizes: Sizes, seed: int) -> None:
    n = len(jax.devices())
    if n != 4:
        raise SystemExit(f"chip_smoke --chips 4: JAX sees {n} devices")
    tcfg = train_config(get_config(ARCH))
    single = train_phase(tcfg, sizes=sizes, seed=seed)
    _report("train unsharded", single.compile_s, single.run_s, single.tokens)
    single.params = None
    sharded = train_phase(tcfg, sizes=sizes, seed=seed,
                          mesh=ops.MeshSpec(dp=2, tp=2))
    _report("train dp=2,tp=2", sharded.compile_s, sharded.run_s,
            sharded.tokens)
    compare_sharded(sharded, single, n)


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-unsharded train phase")
    args = ap.parse_args(argv)
    device = check_device()
    print(f"[device] {device['platform']} {device['kind']} "
          f"x{device['count']}", flush=True)
    if args.chips == 4:
        run_four_chips(Sizes(), args.seed)
    else:
        run_one_chip(Sizes(), args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
