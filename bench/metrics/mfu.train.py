"""mfu.train (model step, moves train_tokens_per_s): forward and
backward model FLOPs per token from the configuration's shapes (no
recompute, causal attention at the mean context) x the run's training
tokens per second / (chips x the chip's bf16 peak), in %."""

from bench.lib import work


def read(run):
    if not run.peak:
        return None
    rate = run.extra["tokens"] / run.extra["window_s"]
    fpt = work.train_flops_per_token(run.conf, run.traffic["seq"])
    return 100.0 * fpt * rate / (run.chips * run.peak["bf16_flops_per_s"])
