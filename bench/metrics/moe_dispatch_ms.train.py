"""moe_dispatch_ms.train (MoE dispatch, moves train_tokens_per_s):
device time of the ops in the program's ``moe.dispatch`` scope (the
sort of (token, expert) pairs, the scatter into the held experts'
buffers, the gather back and the weighted combine, forward and
backward), per step of the window, in ms (``lib.moe_scopes``); nothing
where the step has no such scope."""

from bench.lib import scopes


def read(run):
    split = scopes.step_ms(run)
    return split["moe.dispatch"] if "moe.dispatch" in split else None
