"""mfu_moe.train (model step, moves train_tokens_per_s): forward and
backward model FLOPs of the window's mean step of a latent-attention
MoE model — MLA projections, attention core at the mean causal
context, dense FFN, router, shared experts, head, and the routed
experts' GEMMs on the rows the routers sent to the held experts
(``lib.work_moe``) — over the mean step time / (chips x the chip's bf16
peak), in %."""

from bench.lib import work_moe


def read(run):
    if not run.peak or "moe_rows_routed" not in run.extra:
        return None
    job = run.traffic
    flops = work_moe.train_flops(run.conf, job["seq"],
                                 job["batch"] * job["seq"],
                                 run.extra["moe_rows_routed"])
    step_s = run.extra["window_s"] / run.extra["steps"]
    return 100.0 * flops / step_s / (run.chips * run.peak["bf16_flops_per_s"])
