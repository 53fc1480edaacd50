"""Run one benchmark cell once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine whose JAX sees as many TPU
chips as the cell asks for.  The last line of standard output is the
result as one JSON object; without a TPU the command exits non-zero and
prints no result.
"""

import time

T_START = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    harness.main(t_start=T_START)
