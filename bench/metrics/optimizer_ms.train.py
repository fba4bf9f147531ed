"""optimizer_ms.train (optimizer, moves train_tokens_per_s): device time
of the ops in the program's ``optimizer`` scope (gradient clipping and
the AdamW update), per step of the window, in ms (``lib.scopes``)."""

from bench.lib import scopes


def read(run):
    return scopes.read_ms(run, "optimizer")
