"""Random weights of a Qwen2 decoder, made from the run's seed.

One jitted call makes every leaf on the device, in float32, under the
benchmark's own names.  The system under test gets them through
``system.program_params`` and the plain reference reads them as they
are, so both start from the same numbers without the reference taking
anything the program made.

Leaves (L layers stacked on the leading axis):
  embed (V, d) · wq (L, d, H*hd) · wk, wv (L, d, KV*hd) · wo (L, H*hd, d)
  w_gate, w_up (L, d, F) · w_down (L, F, d) · head (d, V) when untied
  attn_norm, mlp_norm (L, d) · final_norm (d,)
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp


def shapes(c: dict) -> dict:
    """Leaf name -> shape for a configuration file's dict ``c``."""
    d, f, v, n = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  c["num_hidden_layers"])
    hd = d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    out = {"embed": (v, d), "wq": (n, d, q), "wk": (n, d, kv),
           "wv": (n, d, kv), "wo": (n, q, d), "w_gate": (n, d, f),
           "w_up": (n, d, f), "w_down": (n, f, d),
           "attn_norm": (n, d), "mlp_norm": (n, d), "final_norm": (d,)}
    if not c["tie_word_embeddings"]:
        out["head"] = (d, v)
    return out


def seed_key(seed: int):
    """A JAX key from any non-negative seed, also one above 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _init(leaf: str, key, shape):
    if leaf.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if leaf == "embed":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


@functools.lru_cache(maxsize=4)
def _maker(config_json: str):
    c = json.loads(config_json)
    leaves = sorted(shapes(c).items())

    @jax.jit
    def make(key):
        return {name: _init(name, jax.random.fold_in(key, i), shape)
                for i, (name, shape) in enumerate(leaves)}
    return make


def make(c: dict, seed: int) -> dict:
    """Every leaf of configuration ``c`` from ``seed``, in one jitted call."""
    return _maker(json.dumps(c, sort_keys=True))(seed_key(seed))
