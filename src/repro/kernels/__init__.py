# Pallas TPU kernels for the MOSS hot path + the unified dispatch layer.
#
#   dispatch.py    backend selection (pallas / interpret / ref) — the
#                  single entry point for every quantized GEMM; the
#                  custom-VJP in repro.core.linear routes through it
#   mx_fused.py    fused two-level quantize + GEMM (fwd and bwd-dx)
#   mx_gemm.py     microscaled GEMM on pre-quantized operands
#   mx_bwd.py      dW GEMM: fused dequant → transpose → requant along M
#   moe_gmm.py     grouped-expert ragged GEMM (MoE): fused quantize +
#                  all expert GEMMs in one launch + the grouped dW
#   mx_quant.py    standalone fused two-level quantizer
#   mx_tile.py     in-kernel micro-group math shared by the MX kernels
#                  (micro-groups on sublanes) + their exponent layout
#   decode_attn.py fused decode attention over the fp8/bf16 KV cache
#                  (scale application + ring masking + softmax +
#                  combine in one launch — the serving hot path)
#   group_gemm.py  COAT per-group baseline (in-loop dequant)
#   ref.py         pure-jnp oracles (semantics live in repro.core.quant)
#   ops.py         thin public wrappers over dispatch
