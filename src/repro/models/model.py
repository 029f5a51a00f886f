"""Model facade: build the right family, provide uniform batch/IO specs.

Every architecture exposes:
  init(key, dtype) -> params
  forward(params, tokens, **kw) -> (logits, aux)
  hidden_states / logits                   (for vocab-parallel loss paths)
  init_cache(batch, max_len, dtype) -> cache
  prefill(params, tokens, cache, ...) -> (last_logits, cache)
  decode_step(params, token, cache) -> (logits, cache)

``batch_inputs``/``decode_inputs`` build ShapeDtypeStruct stand-ins for
the dry-run (no allocation).  ``run_weights`` builds the rollout's
lower-precision copy of the dot weights (DESIGN.md §Rollout run weights).
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Any, Dict, Iterator, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist.sharding import path_names
from repro.models.encdec import EncDecLM
from repro.models.transformer import LM


def build_model(cfg: ModelConfig, remat: bool = True, remat_policy=None):
    if cfg.is_encdec:
        return EncDecLM(cfg, remat=remat, remat_policy=remat_policy)
    return LM(cfg, remat=remat, remat_policy=remat_policy)


def needs_prefix(cfg: ModelConfig) -> bool:
    return bool(cfg.n_prefix_tokens and cfg.prefix_dim)


# ---------------------------------------------------------------------------
# ShapeDtypeStruct stand-ins (dry-run; never allocates)
# ---------------------------------------------------------------------------

def train_batch_specs(cfg: ModelConfig, shape: ShapeConfig,
                      dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The PPO train-step batch: packed trajectories + RL fields.

    tokens/positions/segment_ids: packed variable-length trajectories.
    advantages: per-token advantage; behav_logprob/prox_logprob: stored
    behavior logprobs and recomputed proximal logprobs (Eq. 5);
    loss_mask: 1 on generated (response) tokens.
    """
    b, s = shape.global_batch, shape.seq_len
    f32 = jnp.float32
    specs = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "positions": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "segment_ids": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "advantages": jax.ShapeDtypeStruct((b, s), f32),
        "behav_logprob": jax.ShapeDtypeStruct((b, s), f32),
        "prox_logprob": jax.ShapeDtypeStruct((b, s), f32),
        "loss_mask": jax.ShapeDtypeStruct((b, s), f32),
    }
    if needs_prefix(cfg):
        specs["prefix_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_prefix_tokens, cfg.prefix_dim), dtype)
    return specs


def prefill_batch_specs(cfg: ModelConfig, shape: ShapeConfig,
                        dtype=jnp.bfloat16) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    specs = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "length": jax.ShapeDtypeStruct((b,), jnp.int32),
    }
    if needs_prefix(cfg):
        specs["prefix_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_prefix_tokens, cfg.prefix_dim), dtype)
    return specs


def decode_batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    b = shape.global_batch
    return {"token": jax.ShapeDtypeStruct((b,), jnp.int32)}


def cache_specs(model, cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytree of the decode cache (eval_shape; no alloc)."""
    return jax.eval_shape(lambda: model.init_cache(batch, max_len, dtype))


def paged_cache_specs(model, cfg: ModelConfig, batch: int, max_len: int,
                      block_size: int, n_blocks: int, dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytrees of the paged decode cache and the (B, E)
    block-table stand-in, E = ceil(max_len / block_size)
    (DESIGN.md §Paged KV-cache pool; no alloc)."""
    cache = jax.eval_shape(
        lambda: model.init_paged_cache(batch, n_blocks, block_size, dtype))
    entries = -(-max_len // block_size)
    tables = jax.ShapeDtypeStruct((batch, max(entries, 1)), jnp.int32)
    return cache, tables


def param_specs(model, cfg: ModelConfig, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: model.init(jax.random.key(0), dtype))


# ---------------------------------------------------------------------------
# Rollout run weights (DESIGN.md §Rollout run weights)
# ---------------------------------------------------------------------------

# Weights whose only consumer is a default-precision dot
# (``layers.matmul``, ``layers.unembed_apply``), by (parent, leaf) path
# name as ``dist/sharding.py`` keys its rules.  Everything else keeps
# its dtype: the gathered embedding table (tied or not), norm scales,
# recurrent and conv cells, MoE experts and routers.
DOT_WEIGHTS = {"attn": ("wq", "wk", "wv", "wo"),
               "mlp": ("w_up", "w_gate", "w_down"),
               "head": ("w",)}
# precisions at which XLA already runs an f32 dot as one bfloat16 pass
_ONE_PASS = (None, "default", "bfloat16")
_SCOPED_RUN: contextvars.ContextVar[Optional[bool]] = contextvars.ContextVar(
    "run_weights", default=None)


def is_dot_weight(path, *, fused_tail: bool = False) -> bool:
    """True for a leaf of ``DOT_WEIGHTS``; ``fused_tail``: the fused
    decode tail's Pallas kernel reads ``attn/wo``, so it is not one."""
    names = path_names(path)
    if len(names) < 2:
        return False
    parent, last = names[-2], names[-1]
    if fused_tail and (parent, last) == ("attn", "wo"):
        return False
    return last in DOT_WEIGHTS.get(parent, ())


def run_weight_dtype():
    """``jnp.bfloat16`` where a default-precision f32 dot is one bfloat16
    pass already: on a TPU (the platform rule of
    ``kernels/ops.py::platform_backend``) with
    ``jax_default_matmul_precision`` unset, ``default`` or ``bfloat16``.
    ``None`` everywhere else, and the weights run as they are."""
    scoped = _SCOPED_RUN.get()
    if scoped is not None:
        return jnp.bfloat16 if scoped else None
    if jax.default_backend() != "tpu":
        return None
    if jax.config.jax_default_matmul_precision not in _ONE_PASS:
        return None
    return jnp.bfloat16


@contextlib.contextmanager
def use_run_weights(on: bool) -> Iterator[None]:
    """Turn the run-weight rule on or off for this thread, whatever the
    platform: for tests, which have no TPU."""
    token = _SCOPED_RUN.set(bool(on))
    try:
        yield
    finally:
        _SCOPED_RUN.reset(token)


@functools.partial(jax.jit, static_argnums=1)
def _cast(leaves, dtype):
    return [x.astype(dtype) for x in leaves]


def run_weights(params, dtype, *, fused_tail: bool = False):
    """``params`` with every dot weight wider than ``dtype`` cast to it,
    all in one jitted program; every other leaf is the same array, not
    a copy.  Returns ``params`` itself when nothing is wider."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [x for _, x in flat]
    idx = [i for i, (path, x) in enumerate(flat)
           if is_dot_weight(path, fused_tail=fused_tail)
           and jnp.dtype(x.dtype).itemsize > jnp.dtype(dtype).itemsize]
    if not idx:
        return params
    for i, y in zip(idx, _cast([leaves[i] for i in idx], jnp.dtype(dtype))):
        leaves[i] = y
    return jax.tree_util.tree_unflatten(treedef, leaves)
