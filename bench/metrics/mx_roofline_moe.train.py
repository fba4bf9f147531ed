"""mx_roofline_moe.train (kernels, moves train_tokens_per_s): the MoE
cell's ``mx_roofline.train``: the least time of every GEMM of the
window's training steps that runs in the MX kernels (forward, input
gradient and weight gradient of the MLA projections, the dense layers'
FFN, the shared experts and the head, at the step's tokens;
``lib.work_moe.mx_gemms``) / the device time of the MX kernels in the
window, in %.  The routers (f32) and the routed experts (the grouped
kernels, ``moe_gmm_roofline.train``) are in neither."""

from bench.lib import harness, work, work_moe
from bench.lib import trace as tr


def read(run):
    if not run.peak:
        return None
    lo, hi = run.window_ns
    is_mx = harness.load_reader("mx_roofline.train").is_mx
    kernel_s = tr.op_ns(run.trace, lo, hi, is_mx) / 1e9
    if kernel_s <= 0:
        return None
    tokens = run.traffic["batch"] * run.traffic["seq"]
    least = sum(work.least_s(ops, nbytes, run.peak)
                for ops, nbytes in work_moe.mx_gemms(run.conf, tokens))
    return 100.0 * least * run.extra["steps"] / kernel_s
