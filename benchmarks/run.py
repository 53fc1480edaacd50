"""Benchmark aggregator: one section per paper table/figure.

  Fig. 6  GEMM throughput by interface          benchmarks.gemm_perf
  Fig. 7  batched 16x16 GEMM vs batch size      benchmarks.batched_gemm_perf
  Fig. 7  grouped ragged expert-GEMM matrix     benchmarks.moe_grouped_perf
  Fig. 8  ||e||_max vs N (+ the +-16 text expt) benchmarks.precision_error
  Fig. 9  error-vs-cost plane                   benchmarks.refine_tradeoff
  (a)     fused attention backend matrix        benchmarks.attention_perf
  (g)     roofline table from dry-run artifacts benchmarks.roofline

Every run also sweeps the backend x policy matrices through the ONE
dispatch layer (the core.ops registry — the exact code paths model
matmuls, attention sublayers and MoE expert FFNs take) and writes them
to ``BENCH_gemm.json`` + ``BENCH_attention.json`` + ``BENCH_moe.json``
at the repo root: tflops + max-abs-error per point, machine-readable
for CI trend tracking.  ``benchmarks.check_regress`` compares them
against the committed ``benchmarks/baselines/`` and FAILS CI on error
regressions or backend-parity drift.

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
CI smoke: PYTHONPATH=src python -m benchmarks.run --point 128
(one small interpret-mode point of each matrix only; seconds, not
minutes).
"""

from __future__ import annotations

import argparse
import json
import os
import time

_ROOT = os.path.join(os.path.dirname(__file__), "..")
BENCH_JSON = os.path.join(_ROOT, "BENCH_gemm.json")
BENCH_ATTN_JSON = os.path.join(_ROOT, "BENCH_attention.json")
BENCH_MOE_JSON = os.path.join(_ROOT, "BENCH_moe.json")
README = os.path.join(_ROOT, "README.md")

# The README capability matrix lives between these markers and is
# REGENERATED from the registry (--update-readme); --check-readme (the
# CI registry-docs job) fails on drift so the docs can't rot.
_README_BEGIN = "<!-- registry-matrix:begin (benchmarks/run.py --update-readme) -->"
_README_END = "<!-- registry-matrix:end -->"


def readme_block() -> str:
    from repro.core import ops
    return f"{_README_BEGIN}\n{ops.capability_markdown()}\n{_README_END}"


def check_readme() -> int:
    """0 when the README matrix matches the registry, else 1."""
    with open(README) as f:
        text = f.read()
    want = readme_block()
    if want in text:
        print("registry-docs: README capability matrix matches the "
              "registry")
        return 0
    if _README_BEGIN not in text or _README_END not in text:
        print("registry-docs: README is missing the registry-matrix "
              "markers; run benchmarks/run.py --update-readme")
        return 1
    print("registry-docs: README capability matrix DRIFTED from the "
          "registry; run benchmarks/run.py --update-readme and commit")
    return 1


def update_readme() -> None:
    with open(README) as f:
        text = f.read()
    start = text.index(_README_BEGIN)
    end = text.index(_README_END) + len(_README_END)
    with open(README, "w") as f:
        f.write(text[:start] + readme_block() + text[end:])
    print(f"README capability matrix regenerated ({README})")


def write_bench_json(matrix: dict) -> str:
    payload = {
        "schema": "bench_gemm/v1",
        "n": matrix["n"],
        "interpret": matrix["interpret"],
        # Mesh attribution (additive): "none" = single-device rows, else
        # the MeshSpec grammar string the sweep routed through.
        "mesh": matrix.get("mesh", "none"),
        "points": [
            {"backend": v["backend"], "policy": v["policy"],
             "tflops": v["tflops"], "max_abs_error": v["max_abs_error"],
             "mean_s": v["mean_s"], "passes": v["passes"]}
            for v in matrix["points"].values()
        ],
    }
    path = os.path.abspath(BENCH_JSON)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def write_attention_json(matrix: dict) -> str:
    payload = {
        "schema": "bench_attention/v1",
        "s": matrix["s"],
        "interpret": matrix["interpret"],
        "mesh": matrix.get("mesh", "none"),
        "points": [
            {"backend": v["backend"], "policy": v["policy"],
             "mask": v["mask"], "tflops": v["tflops"],
             "max_abs_error": v["max_abs_error"],
             "mean_s": v["mean_s"], "passes": v["passes"]}
            for v in matrix["points"].values()
        ],
    }
    path = os.path.abspath(BENCH_ATTN_JSON)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def write_moe_json(matrix: dict) -> str:
    payload = {
        "schema": "bench_moe/v1",
        "t": matrix["t"],
        "e": matrix["e"],
        "interpret": matrix["interpret"],
        "mesh": matrix.get("mesh", "none"),
        "points": [
            {"backend": v["backend"], "policy": v["policy"],
             "profile": v["profile"], "tflops": v["tflops"],
             "max_abs_error": v["max_abs_error"], "mean_s": v["mean_s"],
             "passes": v["passes"], "grouped_util": v["grouped_util"],
             "capacity_util": v["capacity_util"]}
            for v in matrix["points"].values()
        ],
    }
    path = os.path.abspath(BENCH_MOE_JSON)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweeps (CI-sized)")
    ap.add_argument("--point", type=int, default=None, metavar="N",
                    help="CI smoke: run ONLY the backend x policy "
                         "matrices at one small N (interpret mode) and "
                         "write BENCH_gemm.json + BENCH_attention.json")
    ap.add_argument("--list", action="store_true",
                    help="print the op-registry family x impl x "
                         "capability table (the source of every bench "
                         "matrix) and exit")
    ap.add_argument("--check-readme", action="store_true",
                    help="with --list: exit 1 if the README capability "
                         "matrix drifted from the registry (CI "
                         "registry-docs job)")
    ap.add_argument("--update-readme", action="store_true",
                    help="regenerate the README capability matrix from "
                         "the registry")
    args = ap.parse_args()

    if args.list or args.check_readme or args.update_readme:
        from repro.core import ops
        print(ops.format_capability_table())
        if args.update_readme:
            update_readme()
        if args.check_readme:
            raise SystemExit(check_readme())
        return

    from benchmarks import attention_perf, gemm_perf, moe_grouped_perf

    t0 = time.time()
    if args.point is not None:
        matrix = gemm_perf.bench_matrix(n=args.point, reps=1)
        path = write_bench_json(matrix)
        print(f"\nwrote {path} ({len(matrix['points'])} points)")
        amatrix = attention_perf.bench_matrix(s=args.point, reps=1)
        apath = write_attention_json(amatrix)
        print(f"wrote {apath} ({len(amatrix['points'])} points)")
        mmatrix = moe_grouped_perf.bench_matrix(t=args.point, reps=1)
        mpath = write_moe_json(mmatrix)
        print(f"wrote {mpath} ({len(mmatrix['points'])} points) "
              f"— all in {time.time() - t0:.1f}s")
        return

    from benchmarks import batched_gemm_perf, precision_error, refine_tradeoff

    print("#" * 72)
    print("# repro benchmarks — Markidis et al. IPDPSW'18 on TPU terms")
    print("#" * 72)

    if args.quick:
        gemm_perf.run(ns=(256, 512), reps=2)
        matrix = gemm_perf.bench_matrix(n=128, reps=1)
        amatrix = attention_perf.bench_matrix(s=128, reps=1)
        mmatrix = moe_grouped_perf.bench_matrix(t=128, reps=1)
        batched_gemm_perf.run(batches=(256, 1024), reps=2)
        precision_error.run(ns=(512, 1024))
        precision_error.run(ns=(1024,), value_range=16.0)
        refine_tradeoff.run(n=1024, seeds=(0,), reps=2)
    else:
        gemm_perf.run()
        matrix = gemm_perf.bench_matrix()
        amatrix = attention_perf.run(s=256)
        mmatrix = moe_grouped_perf.run(t=256)
        batched_gemm_perf.run()
        precision_error.run()
        precision_error.run(ns=(1024, 4096), value_range=16.0)
        refine_tradeoff.run()
    print(f"\nwrote {write_bench_json(matrix)}")
    print(f"wrote {write_attention_json(amatrix)}")
    print(f"wrote {write_moe_json(mmatrix)}")

    # Roofline table (only if dry-run artifacts exist).
    try:
        from benchmarks import roofline
        rows = roofline.load_all("pod1")
        if rows:
            print("\n== Roofline (single-pod dry-run artifacts) ==")
            print(roofline.to_markdown(rows))
        else:
            print("\n(no dry-run artifacts yet: run "
                  "`PYTHONPATH=src python -m repro.launch.dryrun --all`)")
    except Exception as e:  # roofline needs artifacts; not fatal here
        print(f"\n(roofline table skipped: {e})")

    print(f"\nall benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
