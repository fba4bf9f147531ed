#!/usr/bin/env python3
"""Runs one cell traced, as ``bench/run.py --trace 1`` does, and keeps
what the run reads only in passing: its profiler trace
(``<out>.xplane.pb``) and the compiled step's HLO text that maps ops to
program scopes (``<out>.hlo.txt``, training cells).  Prints the result
line, then the host-chip clock offset the trace allows (``lib.clock``)
and the window's idle gaps named on the chip's clock, beside the names
the run gave them.

  python3 bench/tools/trace_cell.py <workload> <seed> <seconds> <out prefix>
"""

import time

T_START = time.monotonic()

import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main() -> int:
    workload, seed, seconds, out = sys.argv[1:5]
    sys.path.insert(0, str(ROOT))
    from bench.lib import clock, harness, scopes
    from bench.lib import trace as tr

    reduce, compile_step = harness._reduce_trace, scopes.compile_step

    def keep_trace(run, tmp):
        shutil.copy(sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))[-1],
                    out + ".xplane.pb")
        reduce(run, tmp)

    def keep_text(conf, job):
        t = time.monotonic()
        compiled = compile_step(conf, job)
        pathlib.Path(out + ".hlo.txt").write_text(compiled.as_text())
        print(f"the readers' compile and text: {time.monotonic() - t:.3f} s",
              file=sys.stderr)
        return compiled

    harness._reduce_trace, scopes.compile_step = keep_trace, keep_text
    res = harness.run_cell(workload, int(seed), float(seconds), True, T_START)
    print(json.dumps(res), flush=True)
    trace = tr.load(out + ".xplane.pb")
    lo, hi = tr.window(trace)
    off = clock.offset_ns(out + ".xplane.pb")
    print(f"host minus chip clock: {off} ns")
    if off is not None:
        gaps = clock.idle_gaps(trace, lo, hi, sum(off) / 2)
        print("idle gaps on the chip's clock:", json.dumps(gaps))
        print("idle gaps as the run named them:",
              json.dumps(tr.idle_gaps(trace, lo, hi)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
