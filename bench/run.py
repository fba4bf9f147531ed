#!/usr/bin/env python3
"""Runs one cell of the benchmark once and prints its result.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up everything the cell's window will use (set-up),
measures for ``--seconds``, checks what the window produced against the
plain reference, and prints as its last stdout line one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``compared``:
each number the check compared beside its limit.  Exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for, or where the program (``src/``) is not in the checkout.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.lib import device, harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except (device.NoAccelerator, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
