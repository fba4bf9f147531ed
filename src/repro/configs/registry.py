"""--arch id -> config module registry."""

from __future__ import annotations

import importlib

from .base import ModelConfig, SHAPES, shape_applicable

ARCHS: dict[str, str] = {
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe_42b",
    "stablelm-12b": "stablelm_12b",
    "h2o-danube-3-4b": "h2o_danube3_4b",
    "phi3-mini-3.8b": "phi3_mini_38b",
    "minitron-8b": "minitron_8b",
    "musicgen-medium": "musicgen_medium",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "phi-3-vision-4.2b": "phi3_vision_42b",
    "rwkv6-3b": "rwkv6_3b",
    # paper's own models
    "olmo-7b": "olmo_7b",
    "llama2-7b": "llama2_7b",
}

ASSIGNED = [a for a in ARCHS if a not in ("olmo-7b", "llama2-7b")]


def get_config(arch: str, smoke: bool = False,
               layers: int | None = None) -> ModelConfig:
    """The published config (or its smoke reduction), optionally cut to
    ``layers`` layers — widths, heads and vocab stay as they are."""
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCHS)}")
    mod = importlib.import_module(f"repro.configs.{ARCHS[arch]}")
    cfg = mod.SMOKE_CONFIG if smoke else mod.CONFIG
    return cfg if layers is None else cfg.replace(n_layers=layers)


def iter_cells():
    """All (arch, shape, runnable, skip_reason) dry-run cells."""
    for arch in ASSIGNED:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            ok, reason = shape_applicable(cfg, shape)
            yield arch, shape, ok, reason
