"""Pallas TPU paged flash-decoding: one query token vs. a block-table
indexed KV pool.

This is the paged counterpart of ``decode_attention.py`` and the hot
kernel of the paged rollout engine (DESIGN.md §Paged KV-cache pool):
the KV cache is a global pool of N fixed-size blocks, and each slot
owns a *block table* mapping logical block e (absolute positions
[e*bs, (e+1)*bs)) to a physical pool block.  Shared prompt prefixes
point several tables at the same physical block, so the kernel is the
read path for prefix reuse as well.

The grid iterates (slot, table-entry) with the table-entry axis
sequential; every query head of the slot is handled per step, as a
static loop over kv heads, so each pool block is fetched once per slot.
The block table and the per-slot position ``t`` are scalar-prefetch
(SMEM) operands: the BlockSpec index map reads ``tables[b, e]`` to
stream exactly the physical (bs, Hkv*hd) tile the slot references — the
gather happens in the DMA schedule, not in compute.  Blocks are laid out
so that their last two dimensions are whole (8, 128) tiles: queries as
(Hkv, group padded to 8, hd) in f32 per slot, pool blocks as
(bs, Hkv*hd).
Each step folds the tile into online-softmax running statistics.
Masking is purely positional (entry unbound, beyond ``t``, or outside
the sliding window), so partial last blocks, empty slots, and windows
need no special cases.

Oracle: ``repro.kernels.ref.paged_decode_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, t_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, window, bs, ne, hkv, hd):
    ib = pl.program_id(0)
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = tables_ref[ib, e]                              # physical block id
    t = t_ref[ib]
    # positions are implicit in the table entry: entry e holds
    # [e*bs, (e+1)*bs); unbound entries (-1) mask the whole tile.
    pos = e * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = (blk >= 0) & (pos <= t)
    if window > 0:
        mask &= pos > t - window

    # static loop over kv heads: each folds its (group, bs) score tile
    # into the group's online-softmax running statistics.  Head kh's
    # K/V are the lane-aligned columns [kh*hd, (kh+1)*hd) of the tile.
    for kh in range(hkv):
        q = q_ref[kh].astype(jnp.float32)                # (g, hd)
        k = k_ref[:, kh * hd:(kh + 1) * hd].astype(jnp.float32)   # (bs, hd)
        v = v_ref[:, kh * hd:(kh + 1) * hd].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (g, bs)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[kh]                               # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)     # (g, bs)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[kh] = l_ref[kh] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[kh] = acc_ref[kh] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[kh] = m_new

    @pl.when(e == ne - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, t, *,
                                  window=0, softmax_scale=None,
                                  interpret=False):
    """q: (B, H, hd); pools: (N, bs, Hkv, hd); block_tables: (B, E) int32
    (-1 = unbound entry); t: (B,) int32 current absolute position.
    hd must be a multiple of 128 (``ops`` pads it)."""
    b, h, hd = q.shape
    n, bs, hkv, _ = k_pool.shape
    e = block_tables.shape[1]
    g = h // hkv
    gp = -(-g // 8) * 8
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    # Tile-aligned layouts: q head h = kh*g + j becomes (kh, j), the group
    # padded to a multiple of 8 rows in f32 (the query is tiny; a group of
    # 6 bf16 rows is not a layout XLA and Mosaic agree on), and a pool
    # block's heads sit side by side on the lane axis (a free reshape).
    q4 = jnp.pad(q.astype(jnp.float32).reshape(b, hkv, g, hd),
                 ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    kf = k_pool.reshape(n, bs, hkv * hd)
    vf = v_pool.reshape(n, bs, hkv * hd)

    # the paged gather: the physical pool block streamed at step (b, e)
    # is whatever the slot's table names (clamped so unbound -1 entries
    # stay addressable; they are masked out).
    kv_spec = pl.BlockSpec((None, bs, hkv * hd),
                           lambda b_, e_, bt, tt:
                           (jnp.maximum(bt[b_, e_], 0), 0, 0))
    q_spec = pl.BlockSpec((None, hkv, gp, hd), lambda b_, e_, bt, tt: (b_, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # block_tables, t
        grid=(b, e),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, gp, hd), jnp.float32),
            pltpu.VMEM((hkv, gp, 1), jnp.float32),
            pltpu.VMEM((hkv, gp, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, bs=bs, ne=e,
                          hkv=hkv, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q4.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32), t.astype(jnp.int32), q4, kf, vf)
    return out[:, :, :g].reshape(b, h, hd).astype(q.dtype)
