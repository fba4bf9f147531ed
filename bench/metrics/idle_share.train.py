"""idle_share.train (device, moves train_tokens_per_s): the share of
the training window (``bench.window``: steps back to back until the
last one is done) in which no operation ran on the chip, from the
profiler trace, in %."""

from bench.lib import trace as tr


def read(run):
    lo, hi = run.window_ns
    busy = tr.busy_ns(run.trace, lo, hi)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
