"""The chip a run measures on: its peaks, and what JAX reports of it.

Peaks come from one table keyed by ``device_kind``.  A device that is
not in the table is an error: a roofline share against a guessed peak
would be a guess.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect per chip.  The v5e MXU has no fp8 rate, so
# an fp8 GEMM is held against the bf16 peak.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud TPU v5e documentation",
    },
}


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def require_chips(chips: int, allow_cpu: bool = False):
    """The first ``chips`` devices; raises NoAccelerator unless they are
    TPUs (``allow_cpu`` is for the CPU tests of the harness only)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoAccelerator(
            f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds "
                            f"{len(devices)}")
    return devices[:chips]


def describe(devices, program=None) -> dict:
    """platform, kind, count and the fullest chip's peak bytes in use.

    The runtime's ``peak_bytes_in_use`` counts the buffers the process
    holds, not the temporaries a compiled program allocates while it
    runs (a training step's gradients).  ``program``, the window's
    compiled program as (its peak bytes, its argument bytes) from
    ``memory_analysis``, adds them: while it runs a chip holds what is
    in use besides its arguments, plus its peak."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        if program is not None:
            prog_peak, prog_args = program
            other = max(0, int(stats.get("bytes_in_use", 0)) - prog_args)
            peak = max(peak, other + prog_peak)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
