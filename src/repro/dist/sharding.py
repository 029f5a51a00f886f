"""Path-based parameter partition rules and pjit spec builders.

The partitioning scheme (megatron-style TP on the "model" axis, optional
ZeRO/FSDP on the data axes) in one place:

  embeddings       vocab-parallel: ``embed.table`` (V, d) -> ("model", fsdp)
                   and the untied ``head.w`` (d, V) -> (fsdp, "model"); the
                   logits' vocab dim stays on "model" for both.
  attention        column-parallel qkv (output/head dim on "model"),
                   row-parallel ``wo`` (input dim on "model").  GQA-safe:
                   a head count that does not divide the model axis
                   degrades that projection to replication.
  MLP / recurrent  column-parallel up/gate/in projections, row-parallel
                   down/out projections (same rule covers dense MLPs,
                   RG-LRU branches, and the xLSTM cell projections).
  MoE              expert-parallel: the leading expert dim of
                   ``w_up``/``w_gate``/``w_down`` on "model"; the router
                   is replicated (its (T, E) logits feed a top-k over E,
                   which wants E unsharded).
  norms / gains    replicated (every 1-D parameter vector).

Rules are keyed on *path names*, not tree structure, so the same table
covers every config family: stacked per-unit parameters (leading
``n_units`` dim from the scan over layers, or vmapped encoder/decoder
stacks) are handled by right-aligning the canonical rule against the
leaf shape and padding the stacking dims with ``None``.

FSDP (``fsdp=True``): parameters additionally shard one eligible matrix
dim over the data axes (ZeRO-3 — optimizer state inherits the param
specs via ``make_opt_specs``, giving sharded m/v for free).
Divisibility-aware: a dim that the data-axis product does not divide is
simply left unsharded.  ``fsdp_pods=True`` extends the FSDP axes across
the "pod" axis (cross-pod ZeRO for optimizer states that exceed per-pod
HBM).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.constraints import (axes_size, axis_sizes, data_axes,
                                    divisible_data_axes)

# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------


def _model_size(mesh) -> int:
    return axis_sizes(mesh).get("model", 1)


def batch_spec(mesh, global_batch: int):
    """PartitionSpec *entry* for a batch dimension: as many data axes as
    divide ``global_batch`` (outermost dropped first), else None."""
    axes = divisible_data_axes(mesh, global_batch)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def path_names(path) -> Tuple[str, ...]:
    names = []
    for key in path:
        if hasattr(key, "key"):
            names.append(str(key.key))
        elif hasattr(key, "idx"):
            names.append(str(key.idx))
        elif hasattr(key, "name"):
            names.append(str(key.name))
        else:
            names.append(str(key))
    return tuple(names)


# ---------------------------------------------------------------------------
# per-parameter rule
# ---------------------------------------------------------------------------

# column-parallel (output dim on "model") / row-parallel (input dim on
# "model") 2-D projection names, shared across dense MLP, attention
# output path, RG-LRU and xLSTM cells.
_COLUMN_PARALLEL = frozenset({
    "w_up", "w_gate", "w_x", "w_z", "w_rec",
})
_ROW_PARALLEL = frozenset({"w_down", "w_out"})


def _base_rule(cfg: ModelConfig, mesh, names: Tuple[str, ...],
               shape: Sequence[int]) -> Tuple[Optional[str], ...]:
    """Canonical (unstacked) spec for the *trailing* dims of the leaf.

    Returns a tuple whose length is the canonical parameter rank; the
    caller right-aligns it against the actual leaf shape (stacked unit
    params carry leading n_units dims).
    """
    last = names[-1]
    msize = _model_size(mesh)

    # every 1-D parameter (norm scales/biases, gate vectors, lambdas)
    if last in ("scale", "bias", "lam", "f_bias", "i_bias"):
        return (None,)

    if "embed" in names and last == "table":
        vocab_ok = shape[-2] % msize == 0 if len(shape) >= 2 else False
        return ("model" if vocab_ok else None, None)

    if "head" in names and last == "w":
        vocab_ok = shape[-1] % msize == 0
        return (None, "model" if vocab_ok else None)

    if "projector" in names and last == "w":
        return (None, None)

    if "moe" in names:
        if last == "router":
            return (None, None)              # top-k over E wants E unsharded
        if last in ("w_up", "w_gate", "w_down"):
            # (E, d, ff) / (E, ff, d): expert-parallel over "model"
            expert_ok = shape[-3] % msize == 0 if len(shape) >= 3 else False
            return ("model" if expert_ok else None, None, None)

    if last in ("wq", "wk", "wv"):
        # (d, H*hd): column-parallel on heads.  Attention kv projections
        # are GQA-safe; the xLSTM cell's q/k/v all carry cfg.n_heads.
        heads = cfg.n_kv_heads if (last in ("wk", "wv")
                                   and "cell" not in names) else cfg.n_heads
        head_ok = heads % msize == 0 and shape[-1] % msize == 0
        return (None, "model" if head_ok else None)

    if last == "wo":
        heads_ok = cfg.n_heads % msize == 0 and shape[-2] % msize == 0
        return ("model" if heads_ok else None, None)

    if last in _COLUMN_PARALLEL and len(shape) >= 2:
        return (None, "model" if shape[-1] % msize == 0 else None)

    if last in _ROW_PARALLEL and len(shape) >= 2:
        return ("model" if shape[-2] % msize == 0 else None, None)

    if len(shape) == 1:
        return (None,)

    # anything unmatched (conv kernels, slstm gate/recurrence squares,
    # low-rank gate projections, ...) is replicated; FSDP may still
    # shard one of its dims below.
    return tuple(None for _ in shape)


def param_spec(cfg: ModelConfig, mesh, path, leaf, *, fsdp: bool = False,
               fsdp_pods: bool = False) -> P:
    """Full-rank PartitionSpec for one parameter leaf.

    ``path`` is a jax key path (tree_map_with_path); only the key *names*
    are consulted.  ``leaf`` needs only a ``.shape``.
    """
    names = path_names(path)
    shape = tuple(leaf.shape)
    rule = _base_rule(cfg, mesh, names, shape)
    rank = len(shape)
    crank = min(len(rule), rank)
    # right-align the canonical rule; leading (stacking) dims replicated
    entries = [None] * (rank - crank) + list(rule[len(rule) - crank:])

    if fsdp and crank >= 2:
        axes = data_axes(mesh, pods=fsdp_pods)
        if axes:
            fsdp_size = axes_size(mesh, axes)
            entry = axes if len(axes) > 1 else axes[0]
            # first canonical (non-stacking) dim that is unsharded and
            # divisible takes the FSDP axes; none qualifying -> replicated
            for i in range(rank - crank, rank):
                if entries[i] is None and shape[i] % fsdp_size == 0:
                    entries[i] = entry
                    break
    return P(*entries)


# ---------------------------------------------------------------------------
# pytree-level builders
# ---------------------------------------------------------------------------


def make_param_specs(cfg: ModelConfig, mesh, params, *, fsdp: bool = True,
                     fsdp_pods: bool = False):
    """PartitionSpec pytree matching ``params`` (arrays or ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(cfg, mesh, path, leaf, fsdp=fsdp,
                                      fsdp_pods=fsdp_pods),
        params)


def make_opt_specs(param_specs_tree):
    """Optimizer-state specs: m/v inherit the param specs (ZeRO-sharded
    moments when FSDP is on), the step counter is replicated."""
    return {"m": param_specs_tree, "v": param_specs_tree, "step": P()}


def make_train_batch_specs(mesh, batch):
    """Batch-dim data parallelism for every leaf of a train/prefill batch."""
    def spec(leaf):
        return P(batch_spec(mesh, leaf.shape[0]),
                 *([None] * (len(leaf.shape) - 1)))
    return jax.tree.map(spec, batch)


def make_cache_specs(cfg: ModelConfig, mesh, cache):
    """Decode-cache specs: batch dim on the data axes; attention KV heads
    on "model" when the kv-head count divides it (GQA-safe).

    Cache layout (models/model.py): ``units`` leaves are stacked
    (n_units, B, ...) — batch axis 1; ``rem`` leaves and ``t`` are
    batch-major.  Paged pools (``k_pool``/``v_pool``: (..., N, bs, Hkv,
    hd), DESIGN.md §Paged KV-cache pool) have no batch dim — any slot's
    block table may name any physical block, so the pool is the
    per-worker HBM budget, replicated over the data axes with only the
    KV heads on "model"."""
    msize = _model_size(mesh)

    def spec(path, leaf):
        names = path_names(path)
        shape = tuple(leaf.shape)
        rank = len(shape)
        if names[-1] in ("k_pool", "v_pool"):
            entries = [None] * rank
            if shape[-2] % msize == 0:     # shape[-2] IS cfg.n_kv_heads
                entries[-2] = "model"
            return P(*entries)
        bdim = 1 if names and names[0] == "units" and rank >= 2 else 0
        entries = [None] * rank
        entries[bdim] = batch_spec(mesh, shape[bdim])
        # ring-buffer KV: (..., B, W, Hkv, hd) -> heads on "model"
        if (names[-1] in ("k", "v") and rank - bdim == 4
                and shape[-2] % msize == 0 and cfg.n_kv_heads % msize == 0):
            entries[-2] = "model"
        return P(*entries)

    return jax.tree_util.tree_map_with_path(spec, cache)


def named(mesh, specs):
    """Map a PartitionSpec pytree to NamedShardings on ``mesh``."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))
