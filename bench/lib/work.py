"""The work an algorithm needs, from shapes alone: operations and bytes
of a GEMM, the least time it can take on a chip, and a decoder's model
FLOPs per token.

These count what the computation requires, whatever implements it: a
GEMM's logical M, N and K (never padded tiles), and the bytes of its
operands and result read or written once.  A kernel that pads, re-reads
or recomputes shows as a lower roofline share, not as more work.
"""

from __future__ import annotations


def gemm(m: int, n: int, k: int, a_bytes: float, b_bytes: float,
         out_bytes: float) -> tuple[float, float]:
    """(operations, bytes) of ``(m, k) @ (k, n)``: 2mnk, and each operand
    and the result touched once at the given bytes per element."""
    return 2.0 * m * n * k, m * k * a_bytes + k * n * b_bytes + m * n * out_bytes


def least_s(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the bf16 peak and bytes over HBM bandwidth."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def linear_shapes(conf: dict) -> list[tuple[int, int, str]]:
    """(K, N, name) of every linear layer of one decoder layer."""
    d, h, kv = (conf["hidden_size"], conf["num_attention_heads"],
                conf["num_key_value_heads"])
    dh, f = conf["head_dim"], conf["intermediate_size"]
    return [(d, h * dh, "wq"), (d, kv * dh, "wk"), (d, kv * dh, "wv"),
            (h * dh, d, "wo"), (d, f, "w_gate"), (d, f, "w_up"),
            (f, d, "w_down")]


def matmul_params(conf: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's linears and the output head (the embedding is a lookup)."""
    per_layer = sum(k * n for k, n, _ in linear_shapes(conf))
    return (conf["num_hidden_layers"] * per_layer
            + conf["hidden_size"] * conf["vocab_size"])


def attn_flops_per_token(conf: dict, context: float) -> float:
    """Forward score and value products of one token that attends to
    ``context`` positions, over all layers: 2 * 2 * H * Dh * context."""
    return (4.0 * conf["num_hidden_layers"] * conf["num_attention_heads"]
            * conf["head_dim"] * context)


def train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward and backward model FLOPs per token of causal training at
    sequence length ``seq`` (no recompute): 3 x (2 x matmul params +
    attention at the mean causal context (seq + 1) / 2)."""
    fwd = 2.0 * matmul_params(conf) + attn_flops_per_token(conf, (seq + 1) / 2)
    return 3.0 * fwd


def train_gemms(conf: dict, tokens: int) -> list[tuple[float, float]]:
    """(operations, bytes) of every GEMM of one MX training step on
    ``tokens`` rows: per linear layer the forward product (bf16
    activations in, f32 master weight read once for its cast, bf16 out),
    the input gradient (bf16 gradient, fp8 weight, bf16 out) and the
    weight gradient (fp8 activations, bf16 gradient, f32 out)."""
    out = []
    shapes = linear_shapes(conf) * conf["num_hidden_layers"]
    shapes.append((conf["hidden_size"], conf["vocab_size"], "head"))
    for k, n, _ in shapes:
        out.append(gemm(tokens, n, k, 2, 4, 2))        # y = x W
        out.append(gemm(tokens, k, n, 2, 1, 2))        # dx = dy W^T
        out.append(gemm(k, n, tokens, 1, 2, 4))        # dW = x^T dy
    return out

