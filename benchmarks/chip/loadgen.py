"""Open-loop traffic from a mix's parameters and a seed.

The arithmetic follows ``repro.serve.loadgen``: Poisson arrivals and
lognormal prompt and output lengths, clipped to a range.  Two changes
make a run a fixed amount of work:

* the lengths and the gaps between arrivals are the stratified
  quantiles of those distributions (the ``i + 1/2`` over ``n`` points),
  in one balanced order that is the same for every seed: every run of
  ``BLOCK`` arrivals holds one gap, one prompt length and one answer
  length from each ``BLOCK``-th of their sorted values.  A seeded order
  moved the tokens served inside the window by about 2% from seed to
  seed, against 0.1% between two runs of one seed, so the seed draws
  only the prompt tokens;
* times are seconds on the host's monotonic clock, not engine ticks.

A request is due at its arrival time; the runner stamps that due time
on the request, so a late submission counts against latency.  The
generator reports how late it ran.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

__all__ = ["Mix", "Arrival", "rng_for", "quantile_lengths", "schedule",
           "lateness", "BLOCK"]

BLOCK = 16     # arrivals per balanced block
ORDER_SEED = 0  # the order of the arrivals, the same for every run


@dataclasses.dataclass(frozen=True)
class Mix:
    """One serving mix, as its traffic file states it."""

    rate: float                  # requests per second
    prompt_median: float
    prompt_sigma: float
    prompt_min: int
    prompt_max: int
    prompt_round: int            # prompt lengths rounded up to this
    out_median: float
    out_sigma: float
    out_min: int
    out_max: int

    @classmethod
    def from_dict(cls, d: dict) -> Mix:
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})


@dataclasses.dataclass
class Arrival:
    index: int
    due_s: float                 # seconds after the window opens
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one run; any whole number seeds."""
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def quantile_lengths(n: int, median: float, sigma: float, lo: int,
                     hi: int, round_to: int = 1) -> np.ndarray:
    """The ``n`` stratified quantiles of a lognormal, rounded to whole
    tokens, clipped to ``[lo, hi]`` and rounded up to ``round_to``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.clip(np.round(median * np.exp(sigma * z)), lo, hi)
    x = np.ceil(x / round_to) * round_to
    return x.astype(np.int64)


def _gaps(n: int, rate: float) -> np.ndarray:
    """Stratified quantiles of the exponential inter-arrival gap."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _balanced(values: np.ndarray, rng: np.random.Generator,
              block: int = BLOCK) -> np.ndarray:
    """``values`` in an order whose consecutive blocks of ``block`` each
    take one value from every ``block``-th of the sorted values, in a
    seeded order; the shorter last block takes the spare values."""
    v = np.sort(values)
    full, rem = divmod(len(v), block)
    extra = np.zeros(block, bool)
    extra[rng.choice(block, rem, replace=False)] = True
    blocks = [[] for _ in range(full + (rem > 0))]
    start = 0
    for s in range(block):
        size = full + int(extra[s])
        stratum = rng.permutation(v[start:start + size])
        start += size
        for b in range(size):
            blocks[b].append(stratum[b])
    return np.concatenate([rng.permutation(np.asarray(b, v.dtype))
                           for b in blocks])


def schedule(mix: Mix, seconds: float, seed: int, vocab: int,
             ) -> list[Arrival]:
    """Every request due in ``[0, seconds)``: ``round(rate * seconds)``
    requests, the same sizes and gaps in the same order for every seed.
    Prompt ids are drawn from the seed, in ``[2, vocab)``."""
    n = max(1, round(mix.rate * seconds))
    prompts = quantile_lengths(n, mix.prompt_median, mix.prompt_sigma,
                               mix.prompt_min, mix.prompt_max,
                               mix.prompt_round)
    outs = quantile_lengths(n, mix.out_median, mix.out_sigma,
                            mix.out_min, mix.out_max)
    gaps = _gaps(n, mix.rate)
    rng = rng_for(ORDER_SEED, 1)
    prompts, outs, gaps = (_balanced(prompts, rng), _balanced(outs, rng),
                           _balanced(gaps, rng))
    # Scale the gaps so the last request is due inside the window.
    due = np.cumsum(gaps) - gaps[0]
    if due[-1] > 0:
        due = due * min(1.0, seconds * (n - 0.5) / n / due[-1])
    tokens = rng_for(seed, 2)
    return [Arrival(i, float(due[i]),
                    tokens.integers(2, vocab, int(prompts[i]),
                                    dtype=np.int32),
                    int(outs[i]))
            for i in range(n)]


def lateness(due: list[float], sent: list[float]) -> dict[str, float]:
    """How late the generator submitted: p50, p95 and max of
    ``sent - due`` in milliseconds."""
    late = np.maximum(np.asarray(sent) - np.asarray(due), 0.0) * 1e3
    if late.size == 0:
        return {"p50_ms": 0.0, "p95_ms": 0.0, "max_ms": 0.0}
    return {"p50_ms": float(np.percentile(late, 50)),
            "p95_ms": float(np.percentile(late, 95)),
            "max_ms": float(late.max())}
