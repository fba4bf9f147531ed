"""Tiny versions of the benchmark's cells, for the harness's CPU tests:
the same files with every size cut down, and the benchmark's spec."""

from __future__ import annotations

import copy
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_SIZES = {"hidden_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 4, "head_dim": 32,
              "intermediate_size": 256, "vocab_size": 512}


def _load(path):
    return json.loads(pathlib.Path(path).read_text())


def spec() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def files(workload: str) -> tuple:
    """(cell, configuration, job, limits) of ``workload``, cut to a size
    the CPU runs in seconds."""
    cell = next(w for w in spec()["workloads"] if w["name"] == workload)
    conf = copy.deepcopy(_load(BENCH / "configs" / f"{cell['config']}.json"))
    job = dict(_load(BENCH / "traffic" / f"{cell['traffic']}.json"),
               batch=2, seq=64)
    limits = _load(BENCH / "limits" / f"{workload}.json")
    conf.update(TINY_SIZES, num_hidden_layers=2)
    return cell, conf, job, limits
