"""Shared benchmark utilities.

Reporting protocol follows the paper (§VI): per size we run `reps`
timed calls and report the HARMONIC mean of flops/s (equivalently the
arithmetic mean of execution times), with errors omitted below 1%.

Wall-clock numbers are readings of whatever backend JAX runs on. On the
CPU (Pallas in interpret mode) they only rank implementations and show
scaling; they are not device metrics. `tpu_projection` and
benchmarks/roofline.py give MODELLED TPU-v5e numbers from MXU pass
counts and compiled-HLO analysis, labeled as such. A measured chip
number comes only from a run on the chip (see `chip_smoke.py`).
"""

from __future__ import annotations

import json
import os
import time
from collections.abc import Callable

import jax
import numpy as np

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "experiments",
                         "artifacts")

# TPU v5e hardware constants (per chip) — same as the roofline analysis.
PEAK_BF16_TFLOPS = 197.0
HBM_GBPS = 819.0
MXU_RIDGE = PEAK_BF16_TFLOPS * 1e12 / (HBM_GBPS * 1e9)  # flops per byte


def time_fn(fn: Callable[[], jax.Array], reps: int = 5,
            warmup: int = 2) -> dict:
    """Arithmetic-mean wall time (s) + spread over `reps` timed calls."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts = np.asarray(ts)
    return {"mean_s": float(ts.mean()), "min_s": float(ts.min()),
            "spread": float(ts.std() / max(ts.mean(), 1e-12))}


def gemm_flops(m: int, n: int, k: int) -> float:
    """Naive-algorithm op count, as the paper counts them (2*N^3)."""
    return 2.0 * m * n * k


def hmean_tflops(flops: float, mean_s: float) -> float:
    return flops / mean_s / 1e12


def tpu_projection(m: int, n: int, k: int, passes: int,
                   f32_operand_bytes: bool = False) -> dict:
    """Roofline-projected TPU-v5e time for one policy-routed GEMM.

    compute term: passes x (2mnk) / peak;  memory term: operand+result
    HBM traffic (bf16 operands once per pass for the unfused path, f32
    operands once total for the fused path).
    """
    compute_s = passes * gemm_flops(m, n, k) / (PEAK_BF16_TFLOPS * 1e12)
    el = 4 if f32_operand_bytes else 2
    reads = (m * k + k * n) * el * (1 if f32_operand_bytes else passes)
    writes = m * n * 4
    memory_s = (reads + writes) / (HBM_GBPS * 1e9)
    return {"compute_s": compute_s, "memory_s": memory_s,
            "bound": "compute" if compute_s > memory_s else "memory",
            "proj_tflops": gemm_flops(m, n, k) / max(compute_s, memory_s)
                           / 1e12}


def write_json(name: str, payload) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, f"bench_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def print_table(title: str, headers: list[str], rows: list[list]) -> None:
    print(f"\n== {title} ==")
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              for i, h in enumerate(headers)]
    fmt = "  ".join(f"{{:>{w}}}" for w in widths)
    print(fmt.format(*headers))
    for r in rows:
        print(fmt.format(*[str(x) for x in r]))
