#!/usr/bin/env python3
"""Readings for setting the limits of a training cell's correctness
check (``bench/limits``), on the chip at the cell's own size.  Not run
by the benchmark's own runs.

  python3 bench/tools/control.py --workload <name> --seeds 1,2,3 \
      [--program 1] [--control int4:int4:32 ...]

Per seed, each read against the plain reference (f32) as ``correct``
reads the program:
  - with ``--program 1``, the program itself: the cell's compiled step
    driven through its checked steps, as a run's set-up drives it (no
    window), then freed before the reference runs;
  - each control: the reference with every GEMM's operands rounded
    (``fwd:bwd[:group]``; by default the limits file's ``control``);
  - the fault "half of the batch left out, the mean taken over the
    rest", planted in the reference.
Prints one JSON line per seed.
"""

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def parse_rounding(text: str):
    from bench.reference.quantize import Rounding

    fwd, bwd, *group = text.split(":")
    return Rounding(fwd, bwd, int(group[0]) if group else None)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="append", default=[])
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench.lib import check, device, harness, program, train_cell, weights
    from bench.reference import train as ref_train
    from bench.reference.quantize import Rounding

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell, conf, job, limits = harness.cell_files(spec, args.workload)
    ctl = limits["control"]
    controls = ([parse_rounding(c) for c in args.control] or
                [Rounding(ctl["fwd"], ctl["bwd"], ctl.get("group"))])
    program.clear_env()
    devs = device.require_chips(cell["chips"])
    harness.compile_cache()
    feed = jax.jit(lambda k, i: train_cell.batch(conf, job, k, i))
    for seed in (int(s) for s in args.seeds.split(",")):
        key = weights.seed_key(seed)
        out = {"seed": seed, "device": devs[0].device_kind}
        if args.program:
            run = harness.Run(cell=cell, conf=conf, traffic=job,
                              limits=limits, seed=seed, seconds=0.0,
                              tracing=False, t_start=time.monotonic(),
                              devices=devs)
            prog = train_cell.Cell(run, hooks={})
            prog.setup()
            prog.release()
            readings = prog.prog
            del prog, run
            gc.collect()
        batches = [(b["tokens"], b["labels"]) for b in
                   (feed(key, i) for i in range(train_cell.CHECKED_STEPS))]
        ref = ref_train.run(conf, job, key, batches)
        if args.program:
            out["program"] = check.train_readings(readings, ref)
        for r in controls:
            low = ref_train.run(conf, job, key, batches, rounding=r)
            name = ":".join(str(v) for v in r if v is not None)
            out[f"control {name}"] = check.train_readings(low, ref)
        half = ref_train.run(conf, job, key, batches,
                             keep_rows=job["batch"] // 2)
        out["half_batch"] = check.train_readings(half, ref)
        out["reference_loss"] = ref["loss"]
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
