"""attn_core_ms.train (model step: attention core, moves
train_tokens_per_s): device time of the ops in the program's
``attn_core`` scope (scores, softmax and values, forward, recomputed and
backward), per step of the window, in ms (``lib.scopes``)."""

from bench.lib import scopes


def read(run):
    return scopes.read_ms(run, "attn_core")
