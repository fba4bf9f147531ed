"""FP8 / microscaling format constants and helpers.

The OCP MX spec stores level-2 scales in E8M0: an 8-bit biased exponent
with no sign and no mantissa — i.e. exactly the powers of two
2^-127 .. 2^127.  We represent E8M0 values as **int8 exponents** (the
unbiased exponent) and reconstruct the scale with ``exp2``.  This is
bit-equivalent in semantics, trivially portable across backends, and
cheap inside Pallas kernels (an exp2 on the VPU / exponent-add on the
operand path).
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp

# Maximum representable magnitudes (OCP OFP8 spec / paper §2.1).
E4M3_MAX = 448.0
E5M2_MAX = 57344.0

# Smallest normal, used to guard log2 of zero scales.
TINY = 1e-30

# E8M0 exponent range (unbiased).  MOSS subscales live in (0, 1] so the
# used range is [-127, 0], but we keep the full format range available.
E8M0_MIN_EXP = -127
E8M0_MAX_EXP = 127

FP8Format = Literal["e4m3", "e5m2"]


def fp8_max(fmt: FP8Format) -> float:
    return E4M3_MAX if fmt == "e4m3" else E5M2_MAX


def fp8_dtype(fmt: FP8Format):
    return jnp.float8_e4m3fn if fmt == "e4m3" else jnp.float8_e5m2


def cast_fp8(x, fmt: FP8Format):
    """Saturating cast to FP8.

    XLA's convert to e4m3fn produces NaN for out-of-range inputs, so an
    explicit clip implements the saturating semantics hardware quantizers
    (and the paper) use.  The payload leaves through an optimization
    barrier, so it is materialized in fp8: where the cast and an upcast
    land in one fusion, XLA:TPU computes the fusion in f32 and skips the
    rounding (measured on a v5e: the E5M2 gradient compression's
    error-feedback residual came out 7e-8 of the gradient, not 5e-2).
    """
    import jax

    m = fp8_max(fmt)
    return jax.lax.optimization_barrier(
        jnp.clip(x, -m, m).astype(fp8_dtype(fmt)))


def e8m0_encode(ratio):
    """ceil(log2(ratio)) as int8 exponent; ratio expected in (0, 1].

    Matches paper Eq. (3): ``ss_i = 2^ceil(log2(s_i/s))``.  ceil (rather
    than nearest) guarantees ``s * ss_i >= s_i`` so the grouped values
    never overflow the FP8 range after scaling.  The 1e-6 guard keeps
    ulp noise in the ratio from bumping exact powers of two up one
    exponent (the saturating fp8 cast absorbs the ≤1-ulp clip risk).
    """
    r = jnp.maximum(ratio, 2.0 ** -149)   # smallest f32 subnormal: only
    e = jnp.ceil(jnp.log2(r) - 1e-6)      # guards log2(0) -> -inf
    return jnp.clip(e, E8M0_MIN_EXP, E8M0_MAX_EXP).astype(jnp.int8)


def e8m0_decode(exp):
    """int8 exponent -> power-of-two f32 scale, exact over the full
    E8M0 range.  (jnp.exp2(-127) would flush the subnormal result to 0
    on CPU; building the f32 bit pattern directly is exact: 2^-127 is
    the subnormal 0x00400000.)"""
    import jax

    e = exp.astype(jnp.int32)
    normal = (e + 127) << 23
    sub = jnp.int32(0x00400000)            # 2^-127
    bits = jnp.where(e > -127, normal, sub)
    return jax.lax.bitcast_convert_type(bits.astype(jnp.int32),
                                        jnp.float32)


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Quantization recipe for one linear layer (and globally).

    mode:
      - "bf16":       no quantization (the BF16 baseline)
      - "per_tensor": TE-style, one f32 scale per tensor
      - "per_group":  COAT-style, f32 scale per `group_size` along K
      - "moss":       two-level microscaling (level-1 f32 per tensor,
                      level-2 E8M0 per `micro_group` along K)
    weight_scaling:
      - "jit":     max-reduction every step (just-in-time)
      - "delayed": previous step's amax (history window of 1)
      - "auto":    MOSS automatic scaling (predicted, interval refresh)
    """

    mode: Literal["bf16", "per_tensor", "per_group", "moss"] = "moss"
    fwd_format: FP8Format = "e4m3"
    bwd_format: FP8Format = "e5m2"
    micro_group: int = 32          # k2 in the paper
    group_size: int = 128          # COAT per-group baseline size
    weight_scaling: Literal["jit", "delayed", "auto"] = "auto"
    rescale_interval: int = 500    # automatic-scaling refresh interval
    # fp8 gradient all-reduce compression (paper Table 5) + error feedback
    grad_comm_fp8: bool = False
    # cast master weights to bf16 before quantization: halves FSDP
    # weight all-gather bytes when GSPMD hoists the gather above the
    # fp8 cast (§Perf); one extra rounding, << the fp8 noise floor
    weight_cast_bf16: bool = False

    @property
    def quantized(self) -> bool:
        return self.mode != "bf16"


BF16_CONFIG = QuantConfig(mode="bf16")
MOSS_CONFIG = QuantConfig(mode="moss")
PER_TENSOR_CONFIG = QuantConfig(mode="per_tensor", weight_scaling="jit")
PER_GROUP_CONFIG = QuantConfig(mode="per_group", weight_scaling="jit")
