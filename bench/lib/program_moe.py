"""The system under test for a latent-attention MoE configuration
(DeepSeek-V2): the ``repro`` package reached through the same public
entry points as ``bench.lib.program``.  Maps the configuration file
and its weights (``bench.lib.weights_moe``) onto the program's
``ModelConfig`` and parameter tree, and checks that the tree the
program expects is the one it is handed.
"""

from __future__ import annotations

import jax

from . import weights_moe as wm

# configuration key -> ModelConfig field
_SIZES = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv",
          "moe_intermediate_size": "d_ff", "intermediate_size": "dense_ff",
          "vocab_size": "vocab", "n_routed_experts_published": "n_experts",
          "n_routed_experts": "experts_held",
          "n_shared_experts": "n_shared", "num_experts_per_tok": "top_k",
          "norm_topk_prob": "norm_topk", "seq_aux": "seq_aux",
          "first_k_dense_replace": "first_dense", "kv_lora_rank": "kv_lora",
          "qk_nope_head_dim": "q_nope", "qk_rope_head_dim": "q_rope",
          "v_head_dim": "v_head", "rms_norm_eps": "norm_eps",
          "rope_theta": "rope_theta"}
_YARN = {"factor": "yarn_factor",
         "original_max_position_embeddings": "yarn_orig_len",
         "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow",
         "mscale": "yarn_mscale", "mscale_all_dim": "yarn_mscale_all_dim"}
# keys whose other values neither the program nor the reference
# implements: refused, never ignored
IMPLEMENTED = {"hidden_act": ("silu",), "tie_word_embeddings": (False,),
               "attention_bias": (False,), "q_lora_rank": (None,),
               "moe_layer_freq": (1,), "routed_scaling_factor": (1,),
               "scoring_func": ("softmax",), "topk_method": ("greedy",),
               "n_group": (1,), "topk_group": (1,)}


def check_conf(conf: dict, seq: int) -> None:
    """Raise unless both sides implement what ``conf`` states, at
    sequence length ``seq``."""
    for key, values in IMPLEMENTED.items():
        if conf[key] not in values:
            raise ValueError(f"{key} = {conf[key]!r}: the benchmark "
                             f"implements {values}")
    if conf["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"rope_scaling {conf['rope_scaling']['type']!r}: "
                         "the benchmark implements yarn")
    if seq > conf["max_position_embeddings"]:
        raise ValueError(f"sequence {seq} > max_position_embeddings "
                         f"{conf['max_position_embeddings']}")


def model_config(conf: dict):
    """The program's ModelConfig for this configuration file, MOSS
    recipe."""
    from repro.configs.registry import get_config
    from repro.core.formats import QuantConfig

    check_conf(conf, 0)
    if conf["recipe"] != "moss":
        raise ValueError(f"recipe {conf['recipe']!r}")
    cfg = get_config(conf["program_arch"]).replace(
        **{f: conf[k] for k, f in _SIZES.items()},
        **{f: conf["rope_scaling"][k] for k, f in _YARN.items()},
        act="swiglu", norm="rmsnorm", tie_embeddings=False)
    if cfg.family != "mla_moe":
        raise ValueError(f"{conf['program_arch']}: family {cfg.family}")
    return cfg.replace(quant=QuantConfig(
        mode="moss", weight_scaling="auto",
        rescale_interval=conf["rescale_interval"]))


def _attn(L: dict) -> dict:
    a = {k: L[k] for k in ("wq", "w_dkv", "w_kr", "w_uk", "w_uv", "wo")}
    a["kv_norm"] = L["kv_norm.gamma"] - 1     # the program's x*(1+scale)
    return a


def _block(L: dict, dense: bool) -> dict:
    out = {"ln1": {"scale": L["ln1.gamma"] - 1}, "attn": _attn(L),
           "ln2": {"scale": L["ln2.gamma"] - 1}}
    if dense:
        out["ffn"] = {k: L[k] for k in wm.DENSE_FFN}
    else:
        out["moe"] = {"router": L["router"], "w_gate": L["e_gate"],
                      "w_up": L["e_up"], "w_down": L["e_down"]}
        out["shared"] = {"w_gate": L["s_gate"], "w_up": L["s_up"],
                         "w_down": L["s_down"]}
    return out


def segments(conf: dict) -> dict:
    """The program's stacked segments: name -> the layers it holds."""
    k, n = conf["first_k_dense_replace"], conf["num_hidden_layers"]
    return {"dense0": range(0, k), "blocks": range(k, n)}


def make_params(conf: dict, key, dtype, out_shardings=None):
    """The whole model in the program's layout, in one jitted call from
    the seed's key."""
    def build(k):
        tree = {"embed": {"embedding": wm.make_leaf(conf, k, "embed", 0,
                                                    dtype),
                          "head": wm.make_leaf(conf, k, "head", 0, dtype)},
                "final_norm": {"scale": wm.make_leaf(
                    conf, k, "final_norm.gamma", 0, dtype) - 1}}
        for seg, layers in segments(conf).items():
            if not layers:
                continue
            dense = seg == "dense0"
            names = wm.layer_leaf_names(conf, layers.start)
            tree[seg] = _block({n: wm.make_stack(conf, k, n, layers, dtype)
                                for n in names}, dense)
        return tree

    return jax.jit(build, out_shardings=out_shardings)(key)


def locate(conf: dict, tree: dict, flat_name: str):
    """(the program's array, index into its stack or None) behind a
    benchmark leaf name (``layers.i.wq`` style)."""
    if flat_name == "embed":
        return tree["embed"]["embedding"], None
    if flat_name == "head":
        return tree["embed"]["head"], None
    if flat_name == "final_norm.gamma":
        return tree["final_norm"]["scale"], None
    _, i, name = flat_name.split(".", 2)
    seg, layers = next((s, r) for s, r in segments(conf).items()
                       if int(i) in r)
    blk, j = tree[seg], int(i) - layers.start
    if name in ("ln1.gamma", "ln2.gamma"):
        return blk[name.split(".")[0]]["scale"], j
    if name == "kv_norm.gamma":
        return blk["attn"]["kv_norm"], j
    if name in blk["attn"]:
        return blk["attn"][name], j
    if name in wm.DENSE_FFN:
        return blk["ffn"][name], j
    if name == "router":
        return blk["moe"]["router"], j
    part = "moe" if name.startswith("e_") else "shared"
    return blk[part]["w_" + name[2:]], j


def program_leaf(conf: dict, tree: dict, flat_name: str):
    arr, j = locate(conf, tree, flat_name)
    return arr if j is None else arr[j]


def program_form(name: str, value):
    """A benchmark leaf as the program stores it (RMSNorm: gamma - 1)."""
    return value - 1 if name.endswith(".gamma") else value


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
