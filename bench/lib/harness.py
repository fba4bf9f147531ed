"""One run of one cell: find its files by name, check the chip, set up,
measure, check the output against the reference, print the result.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix or training job
(``bench/traffic/<traffic>.json``), whose ``kind`` picks the runner
(``bench/lib/<kind>_cell.py``).  The limits of its correctness check are
in ``bench/limits/<workload>.json``.  Each per-layer metric is a reader
of its own, ``bench/metrics/<metric>.py``.  A later PR adds a cell by
adding such files and a ``workloads`` entry; nothing here names a cell.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from typing import Any

from . import device as devmod

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Run:
    """What one run knows; runners fill it, metric readers read it."""
    cell: dict
    conf: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    tracing: bool
    t_start: float
    chips: int = 1
    devices: Any = None
    peak: dict = field(default_factory=dict)
    end_to_end: dict = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    compared: dict = field(default_factory=dict)    # name -> (value, limit)
    notes: list = field(default_factory=list)       # earlier stdout lines
    trace: Any = None                                # lib.trace.Trace
    window_ns: tuple = (0.0, 0.0)
    extra: dict = field(default_factory=dict)       # the runner's records;
    # "program_bytes": the window's compiled program's (peak, arguments)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, cell: dict, section: str) -> list[dict]:
    """The ``section`` metrics this cell reports."""
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    out = []
    for m in spec[section]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, every program kept (no minimum compile time), so only a
    cell's first run in a checkout compiles."""
    import jax

    path = ROOT / ".jax_cache" / "bench"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


def cell_files(spec: dict, workload: str) -> tuple[dict, dict, dict, dict]:
    """(cell, configuration, traffic, limits) of a workload, by name."""
    cell = find_cell(spec, workload)
    return (cell, load_json(BENCH / "configs" / f"{cell['config']}.json"),
            load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
            load_json(BENCH / "limits" / f"{workload}.json"))


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, allow_cpu: bool = False, hooks=None,
             spec: dict | None = None, files=None) -> dict:
    """Runs the cell; returns the result dict (the last stdout line).
    The harness's own tests pass ``files`` (the cell's four files) and
    ``hooks``, which break the timed path underneath; a benchmark run
    passes neither."""
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(f"no program under {ROOT / 'src'}")
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cell, conf, traffic, limits = files or cell_files(spec, workload)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from . import program
    program.clear_env()

    devices = devmod.require_chips(cell["chips"], allow_cpu=allow_cpu)
    run = Run(cell=cell, conf=conf, traffic=traffic, limits=limits,
              seed=seed, seconds=seconds, tracing=trace, t_start=t_start,
              chips=cell["chips"], devices=devices)
    run.peak = (devmod.peaks(devices[0].device_kind)
                if devices[0].platform == "tpu" else {})
    if devices[0].platform == "tpu":
        compile_cache()
    runner = importlib.import_module(f"bench.lib.{traffic['kind']}_cell")
    cellrun = runner.Cell(run, hooks=hooks or {})
    cellrun.setup()
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            import jax
            jax.profiler.start_trace(tmp)
        try:
            cellrun.measure()
        finally:
            if trace:
                jax.profiler.stop_trace()
        dev = devmod.describe(devices, run.extra.get("program_bytes"))
        cellrun.release()
        gc.collect()
        cellrun.check()
        if trace:
            _reduce_trace(run, tmp)
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    return _result(spec, run, dev)


def _reduce_trace(run: Run, tmp: str) -> None:
    from . import trace as tr

    files = sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    run.trace = tr.load(str(files[-1]))
    run.window_ns = tr.window(run.trace)


def _result(spec: dict, run: Run, dev: dict) -> dict:
    correct = all(math.isfinite(v) and v <= lim
                  for v, lim in run.compared.values())
    correct = correct and run.failed == 0 and bool(run.compared)
    metrics = {}
    if run.tracing:
        from . import trace as tr

        lo, hi = run.window_ns
        for m in metrics_for(spec, run.cell, "per_layer"):
            value = load_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = tr.busy_ns(run.trace, lo, hi)
        dev = dict(dev, busy_s=busy / 1e9, window_s=(hi - lo) / 1e9)
    else:
        for m in metrics_for(spec, run.cell, "end_to_end"):
            metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    for line in run.notes:
        print(line, flush=True)
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": dev}
    if run.tracing:
        from . import trace as tr

        lo, hi = run.window_ns
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr.top_ops(run.trace, lo, hi)],
            "idle_gaps": [[n, s] for n, s in tr.idle_gaps(run.trace, lo, hi)]}
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in run.compared.items()}
    for k, (v, lim) in run.compared.items():
        print(f"compared {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return out
