"""The system under test, as the benchmark drives it: the ``repro``
package of the checkout (``src/``), reached only through its public
entry points — ``configs.registry.get_config``,
``train.steps.make_train_step`` / ``init_train_state``.

This module maps the benchmark's configuration file and its weights
(``bench.lib.weights``) onto the program's config and parameter tree,
and checks that the tree the program expects is the one it is handed.
"""

from __future__ import annotations

import os

import jax

from . import weights

# configuration key -> ModelConfig field
_SIZES = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv", "intermediate_size": "d_ff",
          "vocab_size": "vocab", "head_dim": "d_head",
          "rope_theta": "rope_theta", "norm_eps": "norm_eps",
          "norm": "norm"}


def clear_env() -> None:
    """Drop every ``REPRO_*`` switch: the program runs on its defaults
    (Pallas kernels on a TPU), as the configuration states them."""
    for k in [k for k in os.environ if k.startswith("REPRO_")]:
        del os.environ[k]


# the configuration's ``hidden_act`` of a gated MLP -> ModelConfig.act
_ACT = {"silu": "swiglu"}
# ModelConfig fields the benchmark's weights and reference take as these
# values; an architecture that sets another is refused
_PLAIN = {"family": "dense", "attn_type": "full", "pos_embedding": "rope",
          "rope_pct": 1.0, "qk_norm": False, "logit_softcap": 0.0,
          "embed_scale": False}


def model_config(conf: dict):
    """The program's ModelConfig for this configuration file.  A key the
    benchmark cannot map (another ``hidden_act``, tied embeddings, whose
    weights the benchmark lays out apart) is refused, never ignored."""
    from repro.configs.registry import get_config
    from repro.core.formats import QuantConfig

    if conf["hidden_act"] not in _ACT:
        raise ValueError(f"hidden_act {conf['hidden_act']!r}: the benchmark "
                         f"maps {sorted(_ACT)}")
    if conf["tie_word_embeddings"]:
        raise ValueError("tie_word_embeddings: the benchmark's weights keep "
                         "a separate output head")
    base = get_config(conf["program_arch"])
    cfg = base.replace(**{f: conf[k] for k, f in _SIZES.items()},
                       act=_ACT[conf["hidden_act"]],
                       tie_embeddings=conf["tie_word_embeddings"])
    other = {f: getattr(cfg, f) for f, v in _PLAIN.items()
             if getattr(cfg, f) != v}
    if other:
        raise ValueError(f"{conf['program_arch']}: {other} is not what the "
                         f"benchmark's reference implements")
    recipe = conf["recipe"]
    if recipe == "bf16":
        q = QuantConfig(mode="bf16")
    elif recipe == "moss":
        q = QuantConfig(mode="moss", weight_scaling="auto",
                        rescale_interval=conf.get("rescale_interval", 500))
    else:
        raise ValueError(f"recipe {recipe!r}")
    return cfg.replace(quant=q)


def to_program_tree(conf: dict, w: dict) -> dict:
    """The benchmark's leaves (``weights.make_all``) in the program's
    parameter layout (``repro.models.transformer.model_defs``)."""
    L = w["layers"]
    rms = conf["norm"] != "layernorm"

    def nrm(gamma, beta):
        if rms:                         # the program's RMSNorm is x*(1+scale)
            return {"scale": gamma - 1}
        return {"scale": gamma, "bias": beta}

    return {
        "embed": {"embedding": w["embed"], "head": w["head"]},
        "final_norm": nrm(w["final_norm.gamma"], w.get("final_norm.beta")),
        "blocks": {
            "ln1": nrm(L["ln1.gamma"], L.get("ln1.beta")),
            "attn": {k: L[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": nrm(L["ln2.gamma"], L.get("ln2.beta")),
            "ffn": {k: L[k] for k in ("w_gate", "w_up", "w_down")},
        },
    }


def locate(tree: dict, flat_name: str):
    """(the program's array, layer index or None) behind a benchmark
    leaf name (``layers.i.wq`` style).  Gradients and changes of an
    RMSNorm ``scale`` equal those of the benchmark's gamma."""
    layer = None
    name = flat_name
    if flat_name.startswith("layers."):
        _, i, name = flat_name.split(".", 2)
        layer = int(i)
    part, _, field = name.partition(".")
    norm_field = "scale" if field == "gamma" else "bias"
    if name in ("embed", "head"):
        return tree["embed"]["embedding" if name == "embed" else "head"], None
    if part == "final_norm":
        return tree["final_norm"][norm_field], None
    blk = tree["blocks"]
    if part in ("ln1", "ln2"):
        return blk[part][norm_field], layer
    if part in ("wq", "wk", "wv", "wo"):
        return blk["attn"][part], layer
    return blk["ffn"][part], layer


def program_leaf(tree: dict, flat_name: str):
    """The program's array for a benchmark leaf, sliced to its layer."""
    arr, layer = locate(tree, flat_name)
    return arr if layer is None else arr[layer]


def program_form(conf: dict, name: str, value):
    """A benchmark leaf as the program stores it (RMSNorm: gamma - 1)."""
    if conf["norm"] != "layernorm" and name.endswith(".gamma"):
        return value - 1
    return value


def check_tree(cfg, tree) -> None:
    """Raise unless ``tree`` has exactly the program's parameter layout
    and shapes."""
    from repro.models.layers import abstract_tree
    from repro.models.transformer import model_defs

    want = jax.tree.map(lambda s: s.shape, abstract_tree(model_defs(cfg)))
    got = jax.tree.map(lambda a: a.shape, tree)
    if want != got:
        raise RuntimeError(f"parameter layout differs from the program's:"
                           f" want {want}, got {got}")


def make_params(conf: dict, key, dtype):
    """The whole model on the device, in the program's layout, in one
    jitted call from the seed's key."""
    fn = jax.jit(lambda k: to_program_tree(conf, weights.make_all(conf, k,
                                                                  dtype)))
    return fn(key)

