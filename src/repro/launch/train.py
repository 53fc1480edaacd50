"""End-to-end training driver: data -> sharded train step -> checkpoint
-> restart, with straggler monitoring and elastic mesh selection.

Fault-tolerance contract (the 1000+-node posture, exercised at CPU scale
by examples/ and tests/):

  * checkpoints are atomic + sharded (checkpoint/manager.py); the driver
    resumes from the latest COMPLETE step on any restart — node failure
    and planned restart are the same code path;
  * the mesh is chosen from the SURVIVING device count
    (runtime/mesh.py) so a restart on fewer hosts reshards the same
    checkpoint onto the smaller mesh — and re-resolves the op route
    under the new TP/EP degrees;
  * the data pipeline is stateless-resumable: batch i is a pure function
    of (seed, i), so only the step counter is checkpointed;
  * per-step wall-time telemetry flags stragglers (runtime/monitor.py);
  * optional residual-compensated gradient compression halves DP
    all-reduce wire bytes (optim/compression.py; the paper's Eq. 1).

Recommended XLA flags for real TPU runs (collective/compute overlap —
XLA's latency-hiding scheduler; recorded here, harmless on CPU):
  --xla_tpu_enable_data_parallel_all_reduce_opt=true
  --xla_tpu_data_parallel_opt_different_sized_ops=true
  --xla_enable_async_collective_permute=true

Usage (CPU-scale example):
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b \
      --smoke --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/run1
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCHS, get_config, get_smoke
from repro.configs.base import execution_policy_for
from repro.core import ops
from repro.core.precision import PrecisionPolicy
from repro.data.pipeline import DataConfig, SyntheticLMDataset
from repro.models import api
from repro.optim import adamw
from repro.runtime import mesh as meshlib
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.monitor import StepMonitor, run_header, span
from repro.runtime.sharding import Sharder
from repro.runtime.train_step import make_train_step

__all__ = ["TrainLoop", "main"]


class TrainLoop:
    """Restart-safe training loop over one (config, policy, mesh)."""

    def __init__(self, cfg, *, policy: PrecisionPolicy,
                 opt_cfg: adamw.AdamWConfig, data_cfg: DataConfig,
                 ckpt_dir: str | None = None, microbatches: int = 1,
                 remat: bool = True, ckpt_every: int = 25,
                 use_mesh: bool = False,
                 mesh: "meshlib.MeshSpec | None" = None):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.data_cfg = data_cfg
        self.ckpt_every = ckpt_every
        self.mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self.monitor = StepMonitor()

        # `mesh` is the explicit MeshSpec (--mesh dp=2,tp=2,...);
        # `use_mesh` is the legacy boolean and means --mesh auto.
        spec = mesh
        if spec is None and use_mesh and jax.device_count() > 1:
            spec = meshlib.mesh_spec_for(jax.device_count(), cfg)
        self.mesh = self.sharder = None
        if spec is not None and not spec.is_identity:
            self.mesh = meshlib._mesh_for_spec(spec)
            if isinstance(policy, ops.ExecutionPolicy):
                # Thread the mesh through the policy: routed ops run
                # their shard_map variants, re-validated against each
                # impl's Partitioning capability.
                if policy.mesh != spec:
                    policy = dataclasses.replace(policy, mesh=spec)
                self.sharder = Sharder(cfg, self.mesh, policy=policy)
            else:
                self.sharder = Sharder(cfg, self.mesh)
        self.policy = policy

        step_fn = make_train_step(cfg, opt_cfg, policy,
                                  microbatches=microbatches, remat=remat)
        if self.sharder is not None:
            aparams = jax.eval_shape(
                lambda: api.init_params(jax.random.PRNGKey(0), cfg))
            pspecs = self.sharder.param_specs(aparams)
            ospecs = adamw.AdamWState(
                step=self.sharder.ns(jax.sharding.PartitionSpec()),
                m=pspecs, v=pspecs)
            # out_shardings pinned to the in_shardings: shard_map'd ops
            # may bias XLA toward a different inferred output layout,
            # which trips the donation sharding check on step 2.
            self.jitted_step = jax.jit(
                step_fn, in_shardings=(pspecs, ospecs, None),
                out_shardings=(pspecs, ospecs, None),
                donate_argnums=(0, 1))
        else:
            self.jitted_step = jax.jit(step_fn, donate_argnums=(0, 1))

    def step_fn(self, params, opt, batch):
        """``jitted_step`` (which donates ``params`` and ``opt``),
        dispatched inside the span ``train.step``."""
        with span("train.step"):
            return self.jitted_step(params, opt, batch)

    # ------------------------------------------------------------ state

    def init_or_restore(self, seed: int = 0):
        params = api.init_params(jax.random.PRNGKey(seed), self.cfg)
        opt = adamw.init(params)
        start = 0
        if self.mgr is not None:
            self.mgr.clean_tmp()          # crash garbage from a prior run
            latest = self.mgr.latest_step()
            if latest is not None:
                abstract = jax.eval_shape(lambda: (params, opt))
                params, opt = self.mgr.restore(latest, abstract)
                start = latest
        return params, opt, start

    # -------------------------------------------------------------- run

    def run(self, steps: int, *, seed: int = 0, log_every: int = 10,
            fail_at_step: int | None = None):
        """Train to `steps`. `fail_at_step` injects a crash (tests)."""
        params, opt, start = self.init_or_restore(seed)
        ds = SyntheticLMDataset(self.data_cfg)
        history = []
        ctx = self.mesh if self.mesh is not None else _nullcontext()
        with ctx:
            for i in range(start, steps):
                if fail_at_step is not None and i == fail_at_step:
                    raise RuntimeError(f"injected failure at step {i}")
                batch = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
                self.monitor.start()
                params, opt, metrics = self.step_fn(params, opt, batch)
                # the step has run once its loss is on the host
                history.append(float(metrics["loss"]))
                stats = self.monitor.stop()
                if stats.straggler:
                    print(f"[straggler] step {i}: {stats.last_s:.3f}s "
                          f"vs median {stats.median_s:.3f}s", flush=True)
                if log_every and (i + 1) % log_every == 0:
                    print(f"step {i+1:5d} loss={history[-1]:.4f} "
                          f"gnorm={float(metrics['grad_norm']):.3f} "
                          f"lr={float(metrics['lr']):.2e} "
                          f"{stats.last_s*1e3:.0f}ms", flush=True)
                if self.mgr and (i + 1) % self.ckpt_every == 0:
                    self.mgr.save_async(i + 1, (params, opt))
        if self.mgr:
            self.mgr.wait()
            self.mgr.save(steps, (params, opt))
        return params, opt, history


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--policy", default="bf16")
    ap.add_argument("--logits-policy", default=None)
    ap.add_argument("--backend", action="append", default=None,
                    metavar="[FAMILY=]IMPL",
                    help="op-registry routing, repeatable: "
                         "'family=impl' per kernel family "
                         f"(families: {', '.join(ops.families())}; "
                         "see `python -m benchmarks.run --list`). A "
                         "bare impl name means gemm=IMPL (deprecated). "
                         "Defaults: the arch's backends mapping")
    ap.add_argument("--attn-backend", default=None,
                    help="DEPRECATED: alias for --backend "
                         "attention=IMPL")
    ap.add_argument("--grouped-backend", default=None,
                    help="DEPRECATED: alias for --backend grouped=IMPL")
    ap.add_argument("--tile-cache", default=None, metavar="PATH",
                    help="JSON tile-autotune cache to load now and "
                         "persist autotune results to (also via the "
                         "REPRO_TILE_CACHE env var)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="device mesh: 'dp=2,tp=2,ep=2' (any subset), "
                         "'auto' (fit the visible device count, capped "
                         "at the arch's divisible TP/EP degrees), or "
                         "'none' (default, single-device). Composes "
                         "with --backend: every routed impl must "
                         "declare a Partitioning capability")
    ap.add_argument("--use-mesh", action="store_true",
                    help="DEPRECATED: alias for --mesh auto")
    args = ap.parse_args()

    if args.tile_cache:
        # The flag is both load source and persistence target — it must
        # override any inherited REPRO_TILE_CACHE, or autotune results
        # would save to a different file than the one just loaded.
        os.environ["REPRO_TILE_CACHE"] = args.tile_cache
    n = ops.load_tile_cache()         # flag or inherited REPRO_TILE_CACHE
    if n:
        print(f"tile cache: {n} shape(s) loaded from {ops.tile_cache_path()}")

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    backends = ops.parse_backend_flags(
        args.backend, attn_backend=args.attn_backend,
        grouped_backend=args.grouped_backend)
    mesh_spec = meshlib.resolve_mesh_spec(
        meshlib.resolve_mesh_flag(args.mesh, args.use_mesh), cfg)
    # Route-build validation: training differentiates through every
    # routed op, so demand the vjp capability of each family's impl.
    policy = execution_policy_for(
        cfg, default=args.policy, logits=args.logits_policy,
        backends=backends,
        require={fam: ("vjp",) for fam in ops.families()},
        mesh=mesh_spec)
    print(run_header(args.arch, policy=policy, mesh=policy.mesh), flush=True)
    data_cfg = DataConfig(
        global_batch=args.batch, seq_len=args.seq,
        vocab_size=cfg.vocab_size,
        frames_dim=cfg.d_model if cfg.family == "audio" else 0,
        frames_seq=cfg.encoder_seq if cfg.family == "audio" else 0,
        image_tokens=cfg.num_image_tokens if cfg.family == "vlm" else 0,
        image_dim=cfg.d_model if cfg.family == "vlm" else 0)
    loop = TrainLoop(
        cfg, policy=policy,
        opt_cfg=adamw.AdamWConfig(lr=args.lr, total_steps=args.steps),
        data_cfg=data_cfg, ckpt_dir=args.ckpt_dir,
        microbatches=args.microbatches, ckpt_every=args.ckpt_every,
        mesh=mesh_spec)
    t0 = time.time()
    _, _, hist = loop.run(args.steps)
    print(f"\ntrained {len(hist)} steps in {time.time()-t0:.1f}s; "
          f"loss {hist[0]:.3f} -> {hist[-1]:.3f}")


if __name__ == "__main__":
    main()
