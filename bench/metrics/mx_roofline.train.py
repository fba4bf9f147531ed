"""mx_roofline.train (kernels: the MX GEMM, quantize and weight-gradient
kernels, moves train_tokens_per_s): the least time of every GEMM of the
window's training steps (forward, input gradient, weight gradient of each
linear layer and the head, from their logical M, N, K; ``lib.work``) /
the device time of the MX kernels in the window, in %."""

from bench.lib import trace as tr
from bench.lib import work


MX_KERNELS = ("fused_quant_gemm_pallas", "mx_dw_gemm_pallas",
              "mx_gemm_pallas", "mx_quant_pallas")


def is_mx(family: str) -> bool:
    """The MX kernels, also where XLA names the op after the function
    that wraps the kernel (``jvp_jit_fused_quant_gemm_pallas__``)."""
    return any(k in family for k in MX_KERNELS)


def read(run):
    if not run.peak:
        return None
    lo, hi = run.window_ns
    kernel_s = tr.op_ns(run.trace, lo, hi, is_mx) / 1e9
    if kernel_s <= 0:
        return None
    rows = run.traffic["batch"] * run.traffic["seq"]
    least = sum(work.least_s(ops, nbytes, run.peak)
                for ops, nbytes in work.train_gemms(run.conf, rows))
    return 100.0 * least * run.extra["steps"] / kernel_s
