"""A tiny version of the MoE training cell, for the harness's CPU tests:
the cell's own files with every size cut down (the tiny decoder cells
are in ``tiny``)."""

from __future__ import annotations

import copy

import tiny

WORKLOAD = "train-dsv2lite-moss"

TINY_SIZES = {"hidden_size": 128, "num_attention_heads": 4,
              "kv_lora_rank": 64, "qk_nope_head_dim": 32,
              "qk_rope_head_dim": 32, "v_head_dim": 32,
              "moe_intermediate_size": 64, "intermediate_size": 256,
              "vocab_size": 512, "num_hidden_layers": 3,
              "n_routed_experts": 4, "n_routed_experts_published": 16}


def files(workload: str = WORKLOAD) -> tuple:
    """(cell, configuration, job, limits), cut to a size the CPU runs in
    seconds: 1 dense and 2 MoE layers, 4 of 16 experts held, top-6."""
    cell = next(w for w in tiny.spec()["workloads"]
                if w["name"] == workload)
    conf = copy.deepcopy(tiny._load(tiny.BENCH / "configs"
                                    / f"{cell['config']}.json"))
    conf.update(TINY_SIZES)
    job = dict(tiny._load(tiny.BENCH / "traffic" / f"{cell['traffic']}.json"),
               batch=2, seq=64, reference_query_block=16,
               reference_head_blocks=2)
    limits = tiny._load(tiny.BENCH / "limits" / f"{workload}.json")
    return cell, conf, job, limits
