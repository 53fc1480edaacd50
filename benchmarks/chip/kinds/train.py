"""Training cells: ``TrainLoop.step_fn`` driven as ``TrainLoop.run``
drives it, one ``float(loss)`` per step.

Set-up builds one ``TrainLoop`` (the compiled step) with the benchmark's
weights and the program's AdamW state, and drives it from the seed
through its first ``CHECK_STEPS`` steps on the window's own feed; those
steps compile the step and are what the check compares.  The window
then continues the same object from step ``CHECK_STEPS + 1`` on fresh
rows for ``--seconds``: tokens per second are the tokens of every step
that started in the window over the time until the last of them ended.

The check, once the program's state is freed, runs the plain reference
through the same first steps from the same seed and rows, and compares
each step's loss, every leaf's norm of the first gradient (as the
optimizer got it, worked out from its first moment after step 1), and
every leaf's norm of the change of the parameters over the steps.
"""

from __future__ import annotations

import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights
from benchmarks.chip.spans import Tracer, span

__all__ = ["Runner", "CHECK_STEPS"]

CHECK_STEPS = 3
GRAD_FLOOR = 1e-3      # leaves whose reference gradient norm is under
                       # this share of the median leaf's are not compared


def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


class Runner:
    """One training cell's ``TrainLoop``, feed and readings."""

    def __init__(self, cell):
        from repro.configs.base import execution_policy_for
        from repro.core import ops
        from repro.data.pipeline import DataConfig
        from repro.launch.train import TrainLoop
        from repro.optim import adamw
        from repro.runtime import serve_step

        self.cell = cell
        t = cell.traffic
        self.batch, self.seq = t["batch"], t["seq_len"]
        self.opt_cfg = t["optimizer"]
        cfg = cell.model
        self.vocab = cfg.vocab_size
        policy = execution_policy_for(
            cfg, default=cell.precision,
            require={fam: ("vjp",) for fam in ops.families()})
        self.loop = TrainLoop(
            cfg, policy=policy, opt_cfg=adamw.AdamWConfig(**self.opt_cfg),
            data_cfg=DataConfig(global_batch=self.batch, seq_len=self.seq,
                                vocab_size=self.vocab))
        self.abstract = serve_step.abstract_params(cfg)
        self._make = weights.builder(self.abstract)
        self.feed = self._feed_fn()
        params = self._make(weights.key_for(cell.seed))
        opt = adamw.init(params)
        self.losses, first = [], None
        for i in range(1, CHECK_STEPS + 1):
            params, opt, m = self.loop.step_fn(params, opt, self.feed(i))
            self.losses.append(float(m["loss"]))
            if i == 1:
                first = self._first_grad(opt, m)
        self.grad_norms = first
        self.delta_norms = self._delta(params)
        self.params, self.opt = params, opt
        self.next_step = CHECK_STEPS + 1

    def _feed_fn(self):
        key = weights.key_for(self.cell.seed, 5)
        b, s, v = self.batch, self.seq, self.vocab

        @jax.jit
        def rows(i):
            stream = jax.random.randint(jax.random.fold_in(key, i),
                                        (b, s + 1), 0, v, jnp.int32)
            return {"tokens": stream[:, :-1], "labels": stream[:, 1:]}

        return rows

    def _first_grad(self, opt, metrics):
        """Per-leaf norms of step 1's gradient before clipping, from the
        first moment ``m = (1 - b1) * clip_scale * g``."""
        b1, clip = self.opt_cfg["b1"], self.opt_cfg.get("clip_norm")
        gnorm = float(metrics["grad_norm"])
        scale = 1.0 if clip is None else min(1.0, clip / max(gnorm, 1e-9))
        return [float(n) / (1 - b1) / scale
                for n in jax.jit(_leaf_norms)(opt.m)]

    def _delta(self, params):
        make = self._make

        @jax.jit
        def delta(p, key):
            p0 = make(key)
            return _leaf_norms(jax.tree.map(jnp.subtract, p, p0))

        return [float(x) for x in delta(params, weights.key_for(
            self.cell.seed))]

    # ------------------------------------------------------------ window

    def window(self, seconds: float, trace_dir: str | None):
        step_fn = self.loop.step_fn
        params, opt = self.params, self.opt
        tracer = Tracer(trace_dir, seconds)
        times = []
        i = self.next_step
        t0 = time.monotonic()
        while time.monotonic() < t0 + seconds:
            tracer.poll(time.monotonic() - t0)
            s0 = time.monotonic()
            with span("bench.feed"):
                batch = self.feed(i)
            with span("bench.step"):
                params, opt, m = step_fn(params, opt, batch)
                float(m["loss"])
            times.append((s0, time.monotonic()))
            i += 1
        tracer.close()
        self.tracer = tracer
        self.params, self.opt = params, opt
        self.steps, self.t0 = times, t0
        self.tokens_per_step = self.batch * self.seq
        return self

    @property
    def attempted(self) -> int:
        return len(self.steps)

    @property
    def failed(self) -> int:
        return 0

    def report(self) -> list[str]:
        lines = [f"train steps={len(self.steps)} batch={self.batch} "
                 f"seq={self.seq}", "step_ms " + self._step_times()]
        if getattr(self, "reference", None):
            lines.append("checked " + json.dumps(self.reference))
        return lines

    def _step_times(self, k: int = 5) -> str:
        """Median and longest steps of the window: milliseconds, and
        seconds after the window opened."""
        d = np.array([b - a for a, b in self.steps]) * 1e3
        if not d.size:
            return "none"
        top = np.argsort(d)[::-1][:k]
        return f"p50={np.median(d):.1f} " + " ".join(
            f"{d[i]:.1f}@{self.steps[i][0] - self.t0:.2f}s" for i in top)

    # ------------------------------------------------------------- check

    def free(self):
        self.params = self.opt = self.loop = None
        gc.collect()

    def check(self, reference, limits: dict) -> dict:
        c, opt = self.cell.config, self.opt_cfg

        def step(p, m, v, tokens, labels, i):
            loss, g = reference.loss_and_grads(p, tokens, labels, c)
            p, m, v = reference.adamw(opt, i, p, g, m, v)
            return p, m, v, loss, _leaf_norms(g)

        # one compiled reference step, updating its state in place
        ref_step = jax.jit(step, donate_argnums=(0, 1, 2))
        p = self._make(weights.key_for(self.cell.seed))
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        ref_losses, g1 = [], None
        for i in range(1, CHECK_STEPS + 1):
            rows = self.feed(i)
            p, m, v, loss, norms = ref_step(p, m, v, rows["tokens"],
                                            rows["labels"], i)
            ref_losses.append(float(loss))
            if i == 1:
                g1 = [float(x) for x in norms]
        del m, v
        make = self._make
        ref_delta = [float(x) for x in jax.jit(
            lambda p, key: _leaf_norms(jax.tree.map(
                jnp.subtract, p, make(key))))(p, weights.key_for(
                    self.cell.seed))]
        del p
        med = float(np.median(g1))
        loss_gap = max(abs(a - b) / abs(b)
                       for a, b in zip(self.losses, ref_losses))
        grad_gap = _worst(self.grad_norms, g1, med)
        live = [i for i, g in enumerate(g1) if g >= GRAD_FLOOR * med]
        med_d = float(np.median([ref_delta[i] for i in live]))
        delta_gap = _worst([self.delta_norms[i] for i in live],
                           [ref_delta[i] for i in live], med_d)
        self.reference = {"losses": ref_losses, "program": self.losses,
                          "leaves": len(g1), "leaves_compared": len(live)}
        return {"loss_gap": (loss_gap, limits["loss_gap"]),
                "grad_norm_gap": (grad_gap, limits["grad_norm_gap"]),
                "update_norm_gap": (delta_gap, limits["update_norm_gap"])}


def _worst(prog, ref, median) -> float:
    """Largest |program - reference| over leaves, each against the
    larger of its reference norm and the median leaf's."""
    return max(abs(a - b) / max(b, median) for a, b in zip(prog, ref))
