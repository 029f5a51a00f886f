"""Pallas TPU paged prefill continuation: a CHUNK of query tokens vs. a
block-table indexed KV pool.

This is the multi-query generalization of ``paged_decode_attention.py``
and the hot kernel of chunked prefill (DESIGN.md §Chunked prefill): a
slot resuming ingestion at a nonzero offset attends its chunk of C
queries against every pool block its table names — the history written
by earlier chunks (or by a prefix-sharing leader slot) plus the chunk's
own K/V, which the caller scatters into the pool *before* the attention
call (blocks never wrap, so write-then-read is exact).

The grid iterates (slot, q-head, table-entry) with the table-entry axis
sequential, reusing the block-table gather of the decode kernel: the
table is a scalar-prefetch operand and the BlockSpec index map streams
exactly the physical (bs, hd) tile entry e names.  Heads sit side by
side on the lane axis of the query and pool operands, so every block's
last two dimensions are whole TPU tiles.  Per-query absolute positions
arrive as a (C, 128) VMEM operand (broadcast across lanes); masking is purely positional
(entry unbound, key beyond the query, or outside the sliding window), so
partial blocks, padded queries (q_pos = -1), and windows need no special
cases.  Each step folds its tile into per-query online-softmax running
statistics — the same recurrence as the decode kernel, carried for C
rows instead of one.

Oracle: ``repro.kernels.ref.paged_prefill_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, q_ref, k_ref, v_ref, qpos_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, window, bs, ne):
    ib = pl.program_id(0)
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = tables_ref[ib, e]                              # physical block id
    q = q_ref[...].astype(jnp.float32)                   # (C, hd)
    k = k_ref[...].astype(jnp.float32)                   # (bs, hd)
    v = v_ref[...].astype(jnp.float32)                   # (bs, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # key positions are implicit in the table entry (entry e holds
    # [e*bs, (e+1)*bs)); query positions come from the qpos operand.
    # Unbound entries (-1) and padded queries (q_pos = -1) mask out.
    kpos = e * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    qpos = qpos_ref[...][:, :1]                          # (C, 1)
    mask = (blk >= 0) & (kpos <= qpos) & (qpos >= 0)
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (C, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)         # (C, bs)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(e == ne - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_prefill_attention_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                                   window=0, softmax_scale=None,
                                   interpret=False):
    """q: (B, C, H, hd); pools: (N, bs, Hkv, hd); block_tables: (B, E)
    int32 (-1 = unbound entry); q_pos: (B, C) int32 absolute query
    positions (-1 = padded query row, output unspecified).  C must be a
    multiple of 16 and hd of 128 (``ops`` pads them)."""
    b, c, h, hd = q.shape
    n, bs, hkv, _ = k_pool.shape
    e = block_tables.shape[1]
    group = h // hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    # Tile-aligned layouts: heads side by side on the lane axis (a free
    # reshape; a head is a lane-aligned hd-wide column block), and query
    # positions broadcast across 128 lanes so their block is a whole
    # (C, 128) tile; the kernel reads lane 0.
    qf = q.reshape(b, c, h * hd)
    kf = k_pool.reshape(n, bs, hkv * hd)
    vf = v_pool.reshape(n, bs, hkv * hd)
    qpos = jnp.broadcast_to(q_pos.astype(jnp.int32)[:, :, None], (b, c, 128))

    # the same paged gather as the decode kernel: the physical pool block
    # streamed at (b, h, e) is whatever the slot's table names (clamped;
    # unbound -1 entries are masked out).
    kv_spec = pl.BlockSpec((None, bs, hd),
                           lambda b_, h_, e_, bt, g=group:
                           (jnp.maximum(bt[b_, e_], 0), 0, h_ // g))
    q_spec = pl.BlockSpec((None, c, hd), lambda b_, h_, e_, bt: (b_, 0, h_))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # block_tables
        grid=(b, h, e),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((None, c, 128), lambda b_, h_, e_, bt: (b_, 0, 0)),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((c, hd), jnp.float32),
            pltpu.VMEM((c, 1), jnp.float32),
            pltpu.VMEM((c, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, bs=bs, ne=e),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_tables.astype(jnp.int32), qf, kf, vf, qpos)
    return out.reshape(b, c, h, hd)
