"""head_ms.train (model step: head + loss, moves train_tokens_per_s):
device time of the ops in the program's ``head`` (final norm and output
projection, its GEMMs forward and backward) and ``loss`` scopes, per
step of the window, in ms (``lib.scopes``)."""

from bench.lib import scopes


def read(run):
    return scopes.read_ms(run, "head", "loss")
