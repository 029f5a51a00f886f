"""The one module of the benchmark that knows the system under test.

It turns a configuration file into the program's ``ModelConfig``, the
benchmark's weights into the program's parameter tree, and the
program's trees back into the benchmark's leaf names, so that the
reference and the comparison never import the program.
"""
from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.configs.base import ModelConfig  # noqa: E402
from repro.models.model import build_model  # noqa: E402

_ATTN = ("wq", "wk", "wv", "wo")
_MLP = ("w_gate", "w_up", "w_down")


def model_config(c: dict) -> ModelConfig:
    """The program's config of a configuration file's dict ``c``."""
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]),
        norm_type="rmsnorm", parametric_norm=True, act="swiglu")


def build(c: dict):
    return build_model(model_config(c), remat=False)


def _tree(w: dict, model):
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


def program_params(c: dict, model, w: dict):
    """The benchmark's weights ``w`` in the program's tree, on the device,
    checked leaf for leaf against the tree the program's own init makes.
    The vocabulary's padding rows are zero."""
    want = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    tree = jax.jit(lambda w: _tree(w, model))(w)
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise RuntimeError("the program's parameter tree changed: "
                           f"{jax.tree.structure(want)}")
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


def leaf_norms(c: dict, tree) -> dict:
    """Norm of each leaf of a program tree, on the device, under the
    benchmark's names."""
    return {k: float(v) for k, v in _norms(json.dumps(c, sort_keys=True),
                                           tree).items()}


@functools.partial(jax.jit, static_argnums=0)
def _norms(c_json, tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in leaves(json.loads(c_json), tree).items()}


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())
