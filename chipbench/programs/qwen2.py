"""The program's side of ``model_type`` ``qwen2``: the system's
``ModelConfig`` of a configuration file, the benchmark's leaves
(``reference/qwen2.py``) in the program's parameter tree, and the
program's trees back under the benchmark's names.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import ModelConfig

_ATTN = ("wq", "wk", "wv", "wo")
_MLP = ("w_gate", "w_up", "w_down")


def program_config(c: dict) -> ModelConfig:
    """The program's config of a configuration file's dict ``c``."""
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]),
        norm_type="rmsnorm", parametric_norm=True, act="swiglu")


def program_tree(c: dict, w: dict, model):
    """The benchmark's leaves ``w`` as the program's parameter tree; the
    vocabulary's padding rows are zero."""
    cfg = model.cfg
    pad = cfg.padded_vocab - cfg.vocab_size
    unit = {"attn": {k: w[k] for k in _ATTN},
            "mlp": {k: w[k] for k in _MLP},
            "attn_norm": {"scale": w["attn_norm"]},
            "mlp_norm": {"scale": w["mlp_norm"]}}
    tree = {"embed": {"table": jnp.pad(w["embed"], ((0, pad), (0, 0)))},
            "units": (unit,), "rem": (),
            "final_norm": {"scale": w["final_norm"]}}
    if "head" in w:
        tree["head"] = {"w": jnp.pad(w["head"], ((0, 0), (0, pad)))}
    return tree


def leaves(c: dict, tree) -> dict:
    """The benchmark's names for the leaves of a program tree (params or an
    optimizer moment), with the vocabulary's padding cut off."""
    v = c["vocab_size"]
    unit = tree["units"][0]
    out = {k: unit["attn"][k] for k in _ATTN}
    out.update({k: unit["mlp"][k] for k in _MLP})
    out["embed"] = tree["embed"]["table"][:v]
    if "head" in tree:
        out["head"] = tree["head"]["w"][:, :v]
    out.update(attn_norm=unit["attn_norm"]["scale"],
               mlp_norm=unit["mlp_norm"]["scale"],
               final_norm=tree["final_norm"]["scale"])
    return out
