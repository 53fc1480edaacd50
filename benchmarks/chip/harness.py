"""One run of one cell: set-up, a measured window, a check against the
plain reference, and one JSON line.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by name:

* the cell's entry in ``BENCHMARK.json`` (at the checkout's root) names
  its configuration, its chips and which metrics it reports;
* ``workloads/<cell>.json``: the kind (``serve`` or ``train``), the
  traffic parameters and the limits of the check;
* ``configs/<config>.json``: the configuration as it is run (Hugging
  Face keys), the program's architecture name and the ``ModelConfig``
  fields it replaces, and the names of its ``reference`` and ``costs``
  modules;
* ``kinds/<kind>.py``, ``references/<name>.py``, ``costs/<name>.py``;
* ``metrics/<metric>.py``: one reader per metric, ``read(run)``, which
  returns a number or ``None`` when it finds nothing to read.

So a later change adds a configuration, a mix or a metric by adding
files and a ``BENCHMARK.json`` entry, and edits nothing that is there.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

__all__ = ["Cell", "Run", "load_cell", "main", "HERE", "ROOT"]

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict            # the BENCHMARK.json workload entry
    traffic: dict          # workloads/<cell>.json
    config: dict           # configs/<config>.json
    seed: int
    seconds: float
    precision: str         # the configuration's policy, or the control's
    here: Path
    model: object = None   # the program's ModelConfig, built lazily

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: Cell
    runner: object         # the kind's runner after its window
    costs: object          # costs module of the configuration
    peaks: object          # peaks.Peaks of the device
    setup_s: float
    trace: object = None   # trace.Trace of a --trace 1 run


def load_module(path: Path):
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        ".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, *, seed: int, seconds: float, here: Path = HERE,
              bench: dict, control: bool = False) -> Cell:
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(entries)}")
    entry = entries[name]
    traffic = _json(here / "workloads" / f"{name}.json")
    config = _json(here / "configs" / f"{entry['config']}.json")
    for key in ("config", "chips"):
        if traffic[key] != entry[key]:
            raise SystemExit(f"{name}: workloads/{name}.json says {key}="
                             f"{traffic[key]!r}, BENCHMARK.json says "
                             f"{entry[key]!r}")
    precision = config["precision"]
    if control:
        precision = config["control_precision"]
    return Cell(name=name, entry=entry, traffic=traffic, config=config,
                seed=seed % 2**64, seconds=seconds, precision=precision,
                here=here)


# The program's ModelConfig fields that must equal the configuration
# file's keys: what the benchmark states is what the program runs.
_SAME = {"d_model": "hidden_size", "d_ff": "intermediate_size",
         "num_heads": "num_attention_heads",
         "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
         "num_layers": "num_hidden_layers", "vocab_size": "vocab_size",
         "num_experts": "num_local_experts", "top_k": "num_experts_per_tok",
         "rope_theta": "rope_theta", "window": "sliding_window"}


def build_model(cell: Cell):
    """The program's ModelConfig: its published configuration with the
    configuration file's replacements."""
    from repro.configs import get_config
    from repro.configs.base import Segment

    c = cell.config
    repl = dict(c.get("replace", {}))
    if "segments" in repl:
        repl["segments"] = tuple(Segment(tuple(p), n)
                                 for p, n in repl["segments"])
    model = dataclasses.replace(get_config(c["arch"]), **repl)
    for field, key in _SAME.items():
        want = c.get(key, 0 if key.startswith("num_") else None)
        if key == "head_dim" and want is None:
            want = c["hidden_size"] // c["num_attention_heads"]
        if key == "sliding_window" and want is not None and \
                model.window is None and want >= _context(cell):
            continue   # a window no request reaches: the same model
        if getattr(model, field) != want:
            raise SystemExit(
                f"{cell.entry['config']}: program runs {field}="
                f"{getattr(model, field)!r}, configuration file states "
                f"{key}={want!r}")
    return model


def _context(cell: Cell) -> int:
    t = cell.traffic
    return t.get("max_ctx") or t["seq_len"]


def check_device(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r}; this benchmark does not "
                         f"fall back to it")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every
    program however small."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs lowered or compiled while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.active = False
        self.counts = {e: 0 for e in self.EVENTS}

        def listen(event, duration, **kw):
            if self.active and event in self.counts:
                self.counts[event] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    @property
    def lowered(self) -> int:
        return self.counts[self.EVENTS[0]]

    @property
    def compiled(self) -> int:
        return self.counts[self.EVENTS[1]]


def peak_bytes(device) -> int:
    """The most memory the chip held: the peak of its buffers plus the
    peak it reserved for the programs' temporaries, which a TPU keeps
    apart from ``peak_bytes_in_use``."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0) +
               stats.get("peak_bytes_reserved", 0))


def _metrics_for(bench: dict, section: str, cell: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def _parse(argv):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print one JSON line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the program in the configuration's control "
                         "precision (the check's control; never a "
                         "benchmark run)")
    return ap.parse_args(argv)


def main(argv=None, *, here: Path = HERE, root: Path = ROOT,
         require_tpu: bool = True, t_start: float | None = None) -> dict:
    """Run one cell; print the earlier lines, the checks on standard
    error and the result as the last line of standard output; return
    the result."""
    t_start = time.monotonic() if t_start is None else t_start
    args = _parse(argv)
    bench = _json(root / "BENCHMARK.json")
    cell = load_cell(args.workload, seed=args.seed, seconds=args.seconds,
                     here=here, bench=bench, control=args.control)
    device = check_device(cell.entry["chips"], require_tpu)

    import jax

    from benchmarks.chip import peaks as peaks_mod
    from benchmarks.chip import trace as trace_mod

    peaks = peaks_mod.peaks_for(device["kind"]) if require_tpu else None
    enable_cache(root)
    cell.model = build_model(cell)
    kind = load_module(here / "kinds" / f"{cell.kind}.py")
    runner = kind.Runner(cell)
    setup_s = time.monotonic() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    counter = CompileCounter()
    counter.active = True
    runner.window(cell.seconds, trace_dir)
    counter.active = False
    device["memory_peak_bytes"] = max(
        peak_bytes(d) for d in jax.devices()[:cell.entry["chips"]])
    tr = None
    if trace_dir:
        tr = trace_mod.load(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = trace_mod.busy_s(tr) or 0.0
        device["window_s"] = tr.window_s

    runner.free()
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    reference = load_module(
        here / "references" / f"{cell.config['reference']}.py")
    checks = runner.check(reference, cell.traffic["limits"])
    correct = all(v <= lim for v, lim in checks.values())

    costs = load_module(here / "costs" / f"{cell.config['costs']}.py")
    run = Run(cell=cell, runner=runner, costs=costs, peaks=peaks,
              setup_s=setup_s, trace=tr)
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in _metrics_for(bench, section, cell.name):
        reader = load_module(here / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for line in runner.report():
        print(line)
    print(f"compiles_in_window lowered={counter.lowered} "
          f"compiled={counter.compiled}")
    print("device_memory_stats " + json.dumps(
        jax.devices()[0].memory_stats() or {}))
    print(f"device_bytes_in_use_after_free {in_use}")
    result = {"correct": correct, "attempted": runner.attempted,
              "failed": runner.failed, "metrics": metrics, "device": device}
    if tr is not None:
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    sys.stdout.flush()
    for k, (v, lim) in checks.items():
        print(f"check {k} = {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return result
