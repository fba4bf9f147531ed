#!/usr/bin/env python3
"""Records the small profiler trace the trace reduction is tested on
(``bench/tests/data/small.xplane.pb``), on the chip.

  python3 bench/tools/record_trace.py <out dir>

Inside one ``bench.window`` span: three ``bench.engine_step`` spans,
each running one jitted 2048 x 2048 x 2048 bf16 matmul to completion,
each followed by a ``bench.idle`` span in which the host sleeps 20 ms
and the chip runs nothing.  Writes the trace under ``<out dir>`` and
prints the host-clock length of every span.
"""

import pathlib
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp

    out = pathlib.Path(sys.argv[1])
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    f(a, a).block_until_ready()
    spans = []
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            t = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                f(a, a).block_until_ready()
            spans.append(("bench.engine_step", time.monotonic() - t))
            t = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.02)
            spans.append(("bench.idle", time.monotonic() - t))
    jax.profiler.stop_trace()
    for name, s in spans:
        print(f"{name} {s:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
