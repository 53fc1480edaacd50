"""Serving cells: open-loop traffic through ``ServeEngine.submit`` and
``ServeEngine.step``, the program's own continuous-batching engine.

Set-up builds one engine on the configuration's default route, loads
the benchmark's weights and warms every prompt length the cell's
schedule sends (one short request per length through ``submit`` and
``step``).  The window then submits each request when it is due,
stamping the due time as its submit time, and steps the engine; every
output token is timed on the host's monotonic clock when the step that
made it returns.  After the window the engine keeps stepping, with no
new arrivals, until every request due in the window has its first
token (at most ``DRAIN_S`` more); one that has none by then has failed.

The check runs after the engine's state is freed: a seeded sample of
the requests that finished, the longest among them, goes through the
plain reference over prompt plus served tokens.  At each served token
the gap is how far its reference logit lies below the reference's best
logit at that position.  Two numbers are compared: the mean gap over
the sample's served tokens, and the widest gap over the served tokens
whose position the reference router decides clearly, by a margin of at
least the workload's ``clear_margin`` between the last expert a token
takes and the first it leaves.  Where the margin is smaller, rounding
can flip which experts the token meets, and that token's logits swing
with the flip (``PERF.md`` gives the readings).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import loadgen, weights
from benchmarks.chip.spans import Tracer, span, wrap

__all__ = ["Runner", "DRAIN_S"]

DRAIN_S = 60.0           # seconds past the close to wait for first tokens


@dataclasses.dataclass
class Record:
    """What the harness saw of one request."""
    index: int
    due: float                      # monotonic seconds
    sent: float | None = None
    prompt_len: int = 0
    token_times: list = dataclasses.field(default_factory=list)
    req: object = None


class Runner:
    """One serving cell's engine, schedule and records."""

    def __init__(self, cell):
        from repro.configs.base import execution_policy_for
        from repro.launch.serve import Request, ServeEngine
        from repro.runtime import serve_step

        self.cell = cell
        self.Request = Request
        t = cell.traffic
        self.mix = loadgen.Mix.from_dict(t["mix"])
        self.slots, self.max_ctx = t["slots"], t["max_ctx"]
        cfg = cell.model
        policy = execution_policy_for(
            cfg, default=cell.precision,
            require={"attention": ("decode",)})
        self.engine = ServeEngine(cfg, batch_size=self.slots,
                                  max_ctx=self.max_ctx, policy=policy,
                                  eos_id=-1)
        self.engine.admit = wrap(self.engine.admit, "bench.admit")
        self.engine.tick = wrap(self.engine.tick, "bench.tick")
        self.params = weights.make(serve_step.abstract_params(cfg),
                                   cell.seed)
        self.engine.load(self.params)
        self.arrivals = loadgen.schedule(self.mix, cell.seconds, cell.seed,
                                         cfg.vocab_size)
        self.shapes = sorted({len(a.prompt) for a in self.arrivals})
        self._warm()

    # ------------------------------------------------------------ set-up

    def _warm(self):
        rng = loadgen.rng_for(self.cell.seed, 3)
        eng = self.engine
        for i, n in enumerate(self.shapes):
            eng.submit(self.Request(
                rid=-1 - i, max_new_tokens=2,
                prompt=rng.integers(2, self.cell.model.vocab_size, n,
                                    dtype=np.int32)))
        while not eng.idle:
            eng.step()
        jax.block_until_ready(eng.cache)

    # ------------------------------------------------------------ window

    def window(self, seconds: float, trace_dir: str | None):
        eng, Request = self.engine, self.Request
        recs = [Record(a.index, 0.0, prompt_len=len(a.prompt))
                for a in self.arrivals]
        ticks: list[tuple] = []   # (start, end, tokens ticked, key counts)
        inflight: list[Record] = []
        tracer = Tracer(trace_dir, seconds)
        t0 = time.monotonic()
        close = t0 + seconds
        for r, a in zip(recs, self.arrivals):
            r.due = t0 + a.due_s
        nxt = 0
        while True:
            now = time.monotonic()
            tracer.poll(now - t0)
            with span("bench.submit"):
                while nxt < len(recs) and recs[nxt].due <= now:
                    r, a = recs[nxt], self.arrivals[nxt]
                    r.req = Request(rid=a.index, prompt=a.prompt,
                                    max_new_tokens=a.max_new_tokens)
                    r.req.t_submit = r.due
                    eng.submit(r.req)
                    r.sent = time.monotonic()
                    inflight.append(r)
                    nxt += 1
            if now >= close and all(r.req.out_tokens for r in recs[:nxt]):
                break
            if now >= close + DRAIN_S:
                break
            if eng.idle:
                if now >= close:
                    break
                wake = recs[nxt].due if nxt < len(recs) else close
                with span("bench.wait"):
                    time.sleep(max(0.0, min(wake, close) - time.monotonic()))
                continue
            s0 = time.monotonic()
            with span("bench.step"):
                n = eng.step()
            s1 = time.monotonic()
            with span("bench.record"):
                ctx, still = [], []
                for r in inflight:
                    q = r.req
                    for k in range(len(r.token_times), len(q.out_tokens)):
                        r.token_times.append(q.t_first if k == 0 else s1)
                        if k:   # decoded at prompt + k - 1: prompt + k keys
                            ctx.append(r.prompt_len + k)
                    if not q.done:
                        still.append(r)
                inflight = still
            ticks.append((s0, s1, n, ctx))
        tracer.close()
        self.tracer = tracer
        self.recs, self.ticks, self.t0 = recs, ticks, t0
        self.close = close
        return self

    @property
    def attempted(self) -> int:
        return len(self.recs)

    @property
    def failed(self) -> int:
        return sum(not r.token_times for r in self.recs)

    def report(self) -> list[str]:
        late = loadgen.lateness([r.due for r in self.recs if r.sent],
                                [r.sent for r in self.recs if r.sent])
        lines = [f"prefill_shapes {len(self.shapes)} {self.shapes}",
                 "generator_late_ms " + " ".join(
                     f"{k}={v:.3f}" for k, v in late.items()),
                 f"requests attempted={self.attempted} "
                 f"failed={self.failed} ticks={len(self.ticks)}",
                 "longest_steps " + self._longest()]
        if getattr(self, "checked", None):
            lines.append("checked " + json.dumps(self.checked))
        return lines

    def _longest(self, k: int = 5) -> str:
        """The ``k`` longest engine steps and host gaps between steps:
        milliseconds, seconds after the window opened, and for a step
        the admissions it made."""
        admits = sorted(r.req.t_admit for r in self.recs
                        if r.req is not None and r.req.t_admit is not None)
        steps = sorted(((b - a, a, np.searchsorted(admits, b) -
                         np.searchsorted(admits, a))
                        for a, b, *_ in self.ticks), reverse=True)[:k]
        gaps = sorted(((c - b, b) for (_, b, *_), (c, *_) in
                       zip(self.ticks, self.ticks[1:])), reverse=True)[:k]
        return " ".join(
            [f"step={d * 1e3:.1f}ms@{a - self.t0:.2f}s/admits={n}"
             for d, a, n in steps] +
            [f"gap={d * 1e3:.1f}ms@{b - self.t0:.2f}s" for d, b in gaps])

    # ------------------------------------------------------------- check

    def free(self):
        """Drop the engine's state; the weights are the benchmark's."""
        self.engine.cache = None
        self.engine = None
        gc.collect()

    def check(self, reference, limits: dict) -> dict:
        done = [r for r in self.recs if r.req is not None and r.req.done]
        if not done:
            return {"served_requests": (0.0, 1.0)}
        rng = loadgen.rng_for(self.cell.seed, 4)
        longest = max(done, key=lambda r: len(r.req.out_tokens))
        rest = [r for r in done if r is not longest]
        k = min(len(rest), self.cell.traffic["check_requests"] - 1)
        pick = [longest] + [rest[i] for i in
                            sorted(rng.choice(len(rest), k, replace=False))]
        gaps = _Gaps(reference, self.cell.config, self.max_ctx)
        t = time.monotonic()
        per = [gaps(self.params, np.asarray(r.req.prompt),
                    np.asarray(r.req.out_tokens)) for r in pick]
        every = np.concatenate([g for g, _ in per])
        margin = np.concatenate([m for _, m in per])
        floor = self.cell.traffic["clear_margin"]
        clear = margin >= floor
        widest = int(every.argmax())
        self.checked = {
            "requests": len(pick), "served_tokens": int(every.size),
            "check_s": round(time.monotonic() - t, 3),
            "max_gap": float(every[widest]),
            "router_margin_at_max_gap": float(margin[widest]),
            "max_gap_by_margin": {f"{m:g}": _widest(every, margin >= m)
                                  for m in MARGINS},
            "share_clear": float(clear.mean()),
            "gap_p99": float(np.percentile(every, 99)),
            "share_not_best": float(np.mean(every > 0))}
        return {"mean_logit_gap": (float(every.mean()),
                                   limits["mean_logit_gap"]),
                "clear_max_gap": (_widest(every, clear),
                                  limits["clear_max_gap"])}


# router margins at which the widest gap is printed beside the check
MARGINS = (0.0, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1)


def _widest(gaps, keep) -> float:
    return float(gaps[keep].max()) if keep.any() else 0.0


class _Gaps:
    """Reference logits over one padded sequence (one compile for every
    request) and the widest gap of the served tokens."""

    def __init__(self, reference, config: dict, length: int):
        self.length = length

        def gap(params, tokens, pos, served):
            lg = reference.logits(params, tokens, config)[pos]   # (n, V)
            got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
            if config.get("num_local_experts"):
                margin = reference.router_margin(params, tokens, config)[pos]
            else:
                margin = jnp.ones_like(got)
            return lg.max(-1) - got, margin

        self.fn = jax.jit(gap)

    def __call__(self, params, prompt, out):
        """Per served token, how far its reference logit lies below the
        reference's best at that position, and the reference router's
        margin there."""
        n = len(out)
        seq = np.zeros(self.length, np.int32)
        full = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        seq[:len(full)] = full
        pos = np.zeros(self.length, np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        served = np.zeros(self.length, np.int32)
        served[:n] = out
        gap, margin = self.fn(params, seq, pos, served)
        return np.asarray(gap)[:n], np.asarray(margin)[:n]
