"""The work a latent-attention MoE training step needs (DeepSeek-V2
configurations), from shapes and the rows its router sent to the held
experts: model FLOPs per step, and the operations and bytes of the
routed experts' grouped GEMMs.  As in ``lib.work``: logical sizes only,
each operand and result touched once; a kernel that pads, skips or
re-reads shows as a lower share, not as more work.
"""

from __future__ import annotations

from . import work


def token_shapes(conf: dict, mx_only: bool = False
                 ) -> list[tuple[int, int]]:
    """(K, N) of every GEMM each token goes through, over all layers:
    the MLA projections, the dense layers' FFN, the MoE layers' router
    and shared experts, and the head; with ``mx_only`` all but the
    routers, which stay f32 and out of the MX kernels.  The routed
    experts are counted per routed row apart (``routed_shapes``)."""
    d, h, v = (conf["hidden_size"], conf["num_attention_heads"],
               conf["vocab_size"])
    lora, nope, rope = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                        conf["qk_rope_head_dim"])
    dv, ff = conf["v_head_dim"], conf["intermediate_size"]
    shared = conf["n_shared_experts"] * conf["moe_intermediate_size"]
    mla = [(d, h * (nope + rope)), (d, lora), (d, rope), (lora, h * nope),
           (lora, h * dv), (h * dv, d)]
    out = []
    for i in range(conf["num_hidden_layers"]):
        out += mla
        if i < conf["first_k_dense_replace"]:
            out += [(d, ff), (d, ff), (ff, d)]
        else:
            if not mx_only:
                out.append((d, conf["n_routed_experts_published"]))
            out += [(d, shared), (d, shared), (shared, d)]
    return out + [(d, v)]


def routed_shapes(conf: dict) -> list[tuple[int, int]]:
    """(K, N) of the GEMMs each routed row goes through in one expert."""
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    return [(d, f), (d, f), (f, d)]


def moe_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def attn_flops_per_token(conf: dict, context: float) -> float:
    """Forward score (qk 192) and value (v 128) products of one token
    attending to ``context`` positions, over all layers."""
    qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    return (2.0 * conf["num_hidden_layers"] * conf["num_attention_heads"]
            * (qk + conf["v_head_dim"]) * context)


def train_flops(conf: dict, seq: int, tokens: int, rows_routed: float
                ) -> float:
    """Forward and backward model FLOPs of one training step on
    ``tokens`` rows of ``seq`` whose routers sent ``rows_routed`` (token,
    expert) pairs to the held experts over all MoE layers (no
    recompute): 3 x (2 x MACs of every GEMM + attention at the mean
    causal context (seq + 1) / 2)."""
    dense = sum(k * n for k, n in token_shapes(conf)) * tokens
    routed = sum(k * n for k, n in routed_shapes(conf)) * rows_routed
    attn = attn_flops_per_token(conf, (seq + 1) / 2) * tokens
    return 3.0 * (2.0 * (dense + routed) + attn)


def routed_gemms(conf: dict, rows_routed: float
                 ) -> list[tuple[float, float]]:
    """(operations, bytes) of the routed experts' grouped GEMMs of one
    step, each MoE layer taken to route an equal share of
    ``rows_routed``: per GEMM the forward (bf16 rows in, the held
    experts' f32 master weights read once for their cast, bf16 out),
    the input gradient (bf16 gradient, fp8 weights, bf16 out) and the
    weight gradient (fp8 rows, bf16 gradient, f32 out) — the
    conventions of ``work.train_gemms``."""
    rows = rows_routed / moe_layers(conf)
    held = conf["n_routed_experts"]
    out = []
    for k, n in routed_shapes(conf) * moe_layers(conf):
        for m_, n_, k_, a, b, c in ((rows, n, k, 2, 4, 2),
                                    (rows, k, n, 2, 1, 2)):
            ops, _ = work.gemm(m_, n_, k_, a, b, c)
            out.append((ops, m_ * k_ * a + held * k_ * n_ * b + m_ * n_ * c))
        ops, _ = work.gemm(k, n, rows, 1, 2, 4)
        out.append((ops, rows * k + rows * n * 2 + held * k * n * 4))
    return out


def mx_gemms(conf: dict, tokens: int) -> list[tuple[float, float]]:
    """(operations, bytes) of the GEMMs of one step on ``tokens`` rows
    that run in the MX kernels (``token_shapes(conf, mx_only=True)``):
    each one's forward, input gradient and weight gradient, with the
    byte conventions of ``work.train_gemms``."""
    out = []
    for k, n in token_shapes(conf, mx_only=True):
        out.append(work.gemm(tokens, n, k, 2, 4, 2))   # y = x W
        out.append(work.gemm(tokens, k, n, 2, 1, 2))   # dx = dy W^T
        out.append(work.gemm(k, n, tokens, 1, 2, 4))   # dW = x^T dy
    return out
