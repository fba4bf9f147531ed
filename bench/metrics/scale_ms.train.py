"""scale_ms.train (MOSS scaling, moves train_tokens_per_s): device time
of the ops in the program's ``moss.scale`` scope (predicted weight
scales, the weight casts and transposes, the per-tensor gradient cast,
the scale advance and its refresh), per step of the window, in ms
(``lib.scopes``)."""

from bench.lib import scopes


def read(run):
    return scopes.read_ms(run, "moss.scale")
