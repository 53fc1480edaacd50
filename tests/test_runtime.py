"""Runtime-layer tests: microbatch gradient accumulation equivalence,
sharding rules, elastic mesh selection, straggler monitor, and a
subprocess test that proves the distribution stack compiles on a real
multi-device (forced-host-device) mesh."""

import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.precision import PrecisionPolicy
from repro.models import api
from repro.optim import adamw
from repro.runtime.elastic import choose_mesh_shape
from repro.runtime.monitor import StepMonitor
from repro.runtime.train_step import make_train_step

POLICY = PrecisionPolicy.uniform("bf16")


class TestTrainStep:
    def _setup(self, arch="starcoder2-15b", batch=4, seq=16):
        cfg = get_smoke(arch)
        key = jax.random.PRNGKey(0)
        params = api.init_params(key, cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                    cfg.vocab_size)
        return cfg, params, {"tokens": tokens, "labels": tokens}

    def test_microbatch_equivalence(self):
        """Accumulated GRADIENTS (microbatches=2/4) == full-batch
        gradients up to bf16 forward roundoff. (Post-Adam params are not
        compared: m/sqrt(v) normalization amplifies near-zero grad noise
        to +-lr, which tests nothing about accumulation.)"""
        import repro.runtime.train_step as ts
        cfg, params, batch = self._setup()
        loss_fn = ts.make_loss_fn(cfg, POLICY, remat=False)
        grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        (_, _), g_full = grad_fn(params, batch)

        for mb in (2, 4):
            micro = ts._split_micro(batch, mb)
            g_acc = jax.tree.map(lambda p: np.zeros(p.shape, np.float32),
                                 params)
            losses = []
            for j in range(mb):
                mbatch = jax.tree.map(lambda x: x[j], micro)
                (l, _), g = grad_fn(params, mbatch)
                losses.append(float(l))
                g_acc = jax.tree.map(
                    lambda a, b: a + np.asarray(b, np.float32) / mb,
                    g_acc, g)
            gf = np.concatenate([np.asarray(x, np.float32).ravel()
                                 for x in jax.tree.leaves(g_full)])
            ga = np.concatenate([x.ravel()
                                 for x in jax.tree.leaves(g_acc)])
            # cosine similarity ~ 1 and small relative L2 error
            cos = float((gf * ga).sum()
                        / max(np.linalg.norm(gf) * np.linalg.norm(ga),
                              1e-30))
            rel = float(np.linalg.norm(gf - ga) /
                        max(np.linalg.norm(gf), 1e-30))
            assert cos > 0.999, (mb, cos)
            assert rel < 5e-2, (mb, rel)

    def test_remat_matches_no_remat(self):
        cfg, params, batch = self._setup()
        opt_cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0)
        p1, _, m1 = jax.jit(make_train_step(
            cfg, opt_cfg, POLICY, microbatches=1, remat=False))(
                params, adamw.init(params), batch)
        p2, _, m2 = jax.jit(make_train_step(
            cfg, opt_cfg, POLICY, microbatches=1, remat=True))(
                params, adamw.init(params), batch)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-3, atol=1e-5)

    def test_loss_decreases_over_steps(self):
        """20 steps on a fixed batch must overfit (end-to-end learning)."""
        cfg, params, batch = self._setup(batch=2, seq=12)
        opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=0,
                                    weight_decay=0.0)
        step = jax.jit(make_train_step(cfg, opt_cfg, POLICY,
                                       microbatches=1, remat=False))
        opt = adamw.init(params)
        losses = []
        for _ in range(20):
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.7, losses


class TestElastic:
    def test_multi_pod_shape(self):
        shape, axes = choose_mesh_shape(512)
        assert shape == (2, 16, 16) and axes == ("pod", "data", "model")

    def test_single_pod_shape(self):
        shape, axes = choose_mesh_shape(256)
        assert shape == (16, 16) and axes == ("data", "model")

    def test_degraded_counts(self):
        # 192 devices: model axis stays 16 when divisible
        shape, axes = choose_mesh_shape(192)
        assert shape == (12, 16)
        # tiny/odd counts fall back to model=1
        shape, axes = choose_mesh_shape(7)
        assert shape[0] * shape[1] == 7

    def test_single_device(self):
        shape, _ = choose_mesh_shape(1)
        assert shape == (1, 1)

    def test_cfg_caps_model_axis_at_divisible_degree(self):
        """Satellite: with a config, the model axis never exceeds the
        largest degree dividing the arch's shardable dims (kv heads,
        d_ff, experts) — gemma3-1b has a single KV head, so TP=1."""
        from repro.configs import get_config
        from repro.runtime.mesh import max_parallel_degree
        gemma = get_config("gemma3-1b")        # num_kv_heads=1
        mixtral = get_config("mixtral-8x7b")   # 8 kv heads / 8 experts
        assert max_parallel_degree(gemma, 16) == 1
        assert max_parallel_degree(mixtral, 16) == 8
        assert choose_mesh_shape(256, gemma) == \
            ((256, 1), ("data", "model"))
        assert choose_mesh_shape(256, mixtral) == \
            ((32, 8), ("data", "model"))
        # multi-pod keeps the pod axis, caps only the model axis
        assert choose_mesh_shape(512, mixtral) == \
            ((2, 32, 8), ("pod", "data", "model"))

    def test_cfg_none_preserves_legacy_shapes(self):
        """The no-config path is byte-identical to the pre-dedupe
        elastic.choose_mesh_shape (locked above); cfg=None is explicit."""
        assert choose_mesh_shape(256, None) == choose_mesh_shape(256)


class TestMonitor:
    def test_straggler_flagging(self):
        mon = StepMonitor(window=50, z_threshold=4.0)
        for _ in range(20):
            mon.start()
            mon._t0 -= 0.010  # simulate exactly 10ms
            s = mon.stop()
            assert not s.straggler
        mon.start()
        mon._t0 -= 0.500      # 50x step time: must flag
        s = mon.stop()
        assert s.straggler

    def test_mfu_accounting(self):
        mon = StepMonitor(model_flops_per_step=1e12)
        mon.start()
        mon._t0 -= 1.0
        s = mon.stop()
        assert s.achieved_tflops == pytest.approx(1.0, rel=0.05)

    def test_even_window_median_is_two_point(self):
        """Regression: stop() used ts[n // 2], the UPPER of the middle
        pair, for even windows — inflating the median and the MAD scale
        the z-score divides by. [1, 2, 3, 10] ms must give median
        2.5 ms (not 3) and MAD 1.0 ms (not 2)."""
        mon = StepMonitor(window=8)
        for dt in (0.001, 0.002, 0.003, 0.010):
            s = mon.observe(dt)
        assert s.median_s == pytest.approx(0.0025)
        # |t - 2.5| sorted = [0.5, 0.5, 1.5, 7.5] -> two-point 1.0
        assert s.mad_s == pytest.approx(0.001)
        # odd window: plain middle element
        s = mon.observe(0.004)
        assert s.median_s == pytest.approx(0.003)

    def test_train_loop_times_the_step_to_its_loss(self):
        """TrainLoop.run stops the monitor once the loss is on the
        host, so the step it times is the step that ran, not its
        dispatch; the dispatch is the span ``train.step``."""
        from repro.data.pipeline import DataConfig
        from repro.launch.train import TrainLoop
        from repro.runtime.monitor import recent_spans, span

        cfg = get_smoke("starcoder2-15b")
        loop = TrainLoop(cfg, policy=POLICY, opt_cfg=adamw.AdamWConfig(),
                         data_cfg=DataConfig(global_batch=2, seq_len=8,
                                             vocab_size=cfg.vocab_size))

        class SlowLoss:   # a loss the device is still computing
            def __float__(self):
                time.sleep(0.05)
                return 1.0

        def step(params, opt, batch):
            return params, opt, {"loss": SlowLoss()}

        loop.jitted_step = step
        with span("mark") as mark:
            pass
        loop.run(3, log_every=0)
        assert len(loop.monitor.times) == 3
        assert min(loop.monitor.times) >= 0.05
        steps = [sp for sp in recent_spans()
                 if sp[3] > mark.id and sp[0] == "train.step"]
        assert len(steps) == 3


MESH_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke, input_specs
    from repro.configs.base import ShapeSpec
    from repro.core.precision import PrecisionPolicy
    from repro.launch.mesh import make_test_mesh
    from repro.models import api
    from repro.optim import adamw
    from repro.runtime import serve_step as serve
    from repro.runtime.sharding import Sharder
    from repro.runtime.train_step import make_train_step

    assert jax.device_count() == 16
    mesh = make_test_mesh(data=4, model=4)
    for arch in ("gemma3-1b", "mixtral-8x7b", "zamba2-7b", "rwkv6-7b",
                 "whisper-medium", "internvl2-76b"):
        cfg = get_smoke(arch)
        sh = Sharder(cfg, mesh)
        shape = ShapeSpec("t", 32, 8, "train")
        specs = input_specs(cfg, shape)
        aparams = serve.abstract_params(cfg)
        pspecs = sh.param_specs(aparams)
        aopt = jax.eval_shape(adamw.init, aparams)
        ospecs = adamw.AdamWState(
            step=sh.ns(jax.sharding.PartitionSpec()),
            m=sh.param_specs(aopt.m), v=sh.param_specs(aopt.v))
        fn = make_train_step(cfg, adamw.AdamWConfig(),
                             PrecisionPolicy.uniform("bf16"),
                             microbatches=2, remat=True)
        with mesh:
            lowered = jax.jit(fn, in_shardings=(
                pspecs, ospecs, sh.batch_specs(specs))).lower(
                    aparams, aopt, specs)
            compiled = lowered.compile()
        from repro.analysis.hlo_cost import compiled_cost
        assert compiled_cost(compiled)["flops"] > 0
        print("mesh-compile ok:", arch, flush=True)
    print("ALL_OK")
""")


@pytest.mark.slow
def test_sharded_train_step_compiles_on_mesh():
    """Subprocess (own jax runtime with 16 forced host devices): the
    sharded train step must lower+compile for a mix of families on a
    (data=4, model=4) mesh — the small-scale twin of the dry-run."""
    r = subprocess.run(
        [sys.executable, "-c", MESH_PROG], capture_output=True, text=True,
        timeout=900, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "JAX_PLATFORMS": "cpu"})
    assert "ALL_OK" in r.stdout, f"stdout:{r.stdout[-2000:]}\nstderr:{r.stderr[-4000:]}"
