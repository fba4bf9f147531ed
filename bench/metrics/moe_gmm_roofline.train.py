"""moe_gmm_roofline.train (kernels, moves train_tokens_per_s): the least
time of the routed experts' grouped GEMMs of the window's steps
(forward, input gradient and weight gradient of the up, gate and down
projections, on the rows the routers sent to the held experts, never
the rows dispatch allocates; ``lib.work_moe``) / the device time of the
grouped kernels ``moe_gmm_pallas`` and ``moe_dw_gemm_pallas`` in the
window, in %."""

from bench.lib import trace as tr
from bench.lib import work, work_moe

KERNELS = ("moe_gmm_pallas", "moe_dw_gemm_pallas")


def is_grouped(family: str) -> bool:
    return any(k in family for k in KERNELS)


def read(run):
    if not run.peak or "moe_rows_routed" not in run.extra:
        return None
    lo, hi = run.window_ns
    kernel_s = tr.op_ns(run.trace, lo, hi, is_grouped) / 1e9
    if kernel_s <= 0:
        return None
    least = sum(work.least_s(ops, nbytes, run.peak) for ops, nbytes in
                work_moe.routed_gemms(run.conf, run.extra["moe_rows_routed"]))
    return 100.0 * least * run.extra["steps"] / kernel_s
