"""Model configuration dataclass + input-shape registry.

Every assigned architecture gets one module in this package exporting
``CONFIG`` (the full published config) and ``SMOKE_CONFIG`` (a reduced
same-family config for CPU smoke tests).  ``repro.configs.registry``
maps ``--arch`` ids to them.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

from repro.core.formats import QuantConfig, MOSS_CONFIG


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["dense", "moe", "mla_moe", "hybrid", "ssm",
                    "audio", "vlm"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    d_head: int = 0                    # 0 -> d_model // n_heads

    # --- attention ---
    attn_type: Literal["full", "swa", "local"] = "full"
    window: int = 4096
    rope_theta: float = 10_000.0
    rope_pct: float = 1.0              # partial rotary (stablelm: 0.25)
    # YaRN rope scaling (arXiv:2309.00071) of MLA's rope dims; factor 0
    # is plain rope
    yarn_factor: float = 0.0
    yarn_orig_len: int = 4096          # original_max_position_embeddings
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    qk_norm: bool = False
    logit_softcap: float = 0.0         # gemma-style final-logit softcap

    # --- FFN ---
    act: Literal["swiglu", "geglu", "gelu_mlp", "relu2"] = "swiglu"
    norm: Literal["rmsnorm", "layernorm"] = "rmsnorm"

    # --- MoE ---
    n_experts: int = 0                 # routed experts the router scores
    experts_held: int = 0              # experts [0, H) on this chip; 0: all
    n_shared: int = 0
    top_k: int = 0
    norm_topk: bool = True             # renormalise the top-k weights
    seq_aux: bool = False              # balance loss per sequence, not batch
    capacity_factor: float = 1.3       # dispatch outside training
    dense_ff: int = 0                  # width of non-MoE FFN layers
    first_dense: int = 0               # leading layers with dense FFN

    # --- MLA (deepseek) ---
    kv_lora: int = 0
    q_nope: int = 128
    q_rope: int = 64
    v_head: int = 128

    # --- hybrid (recurrentgemma) ---
    block_pattern: tuple[str, ...] = ("attn",)   # repeating unit
    lru_width: int = 0
    conv_width: int = 4

    # --- rwkv ---
    rwkv_head_dim: int = 64
    ddlerp_rank: int = 32
    decay_rank: int = 64

    # --- io / misc ---
    input_mode: Literal["tokens", "embeddings"] = "tokens"
    pos_embedding: Literal["rope", "sinusoidal", "none"] = "rope"
    tie_embeddings: bool = False
    embed_scale: bool = False          # gemma: scale embeddings by sqrt(d)
    norm_eps: float = 1e-5

    # --- training-shape knobs ---
    attn_chunk: int = 512              # flash-chunk size (queries and kv)
    # KV-cache storage for the decode-bound serving shapes: fp8 (e4m3
    # payload + per-(token, kv-head) f32 scales) by default — decode is
    # memory-roofline-bound and the cache read dominates, so 1 byte/
    # element ~halves step HBM traffic (benchmarks/roofline.py).
    # "bf16" is the exactness escape hatch; REPRO_KV_CACHE overrides
    # either way at cache init (models/attention.py).  Training never
    # builds caches, so this has no effect on the training path; MLA's
    # absorbed latent cache ignores it.
    kv_cache_dtype: Literal["bf16", "fp8"] = "fp8"
    # small-T prefill without a mesh: masked dense experts (decode and
    # verify always take them; training never does)
    moe_decode_dense: bool = True
    remat: bool = True
    scan_layers: bool = True

    # quantization recipe (the paper's contribution; swap for baselines)
    quant: QuantConfig = MOSS_CONFIG

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Supports long_500k: SSM / hybrid / sliding-window archs."""
        return (self.family in ("ssm", "hybrid")
                or self.attn_type in ("swa", "local"))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


# The assigned input-shape set (same four for every LM arch).
SHAPES: dict[str, ShapeConfig] = {
    "train_4k":    ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k":   ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether (arch, shape) is a runnable cell; reason when skipped."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("pure full-attention arch: 500k dense-KV decode is "
                       "the quadratic regime this shape excludes "
                       "(DESIGN.md §6)")
    return True, ""
