"""Pallas TPU fused decode tail: paged KV gather + online-softmax
attention + output projection in ONE kernel (DESIGN.md §Fused decode
tail).

``paged_decode_attention`` iterates (slot, q-head, table-entry) and
returns per-head contexts that the model then reshapes and projects with
a separate ``wo`` matmul — a (B, H, hd) round-trip through HBM on every
decode step of the hottest loop in the system.  This kernel processes
ALL query heads of a slot per grid step, so when the sequential
table-entry axis finishes the accumulated per-head contexts are still in
VMEM and the output projection folds in before anything is written back:
the kernel's output is the block's (B, D) projected residual
contribution, not attention contexts.

The grid is (slot, table-entry) with the entry axis sequential.  Like
``paged_decode_attention``, the block table and per-slot position ``t``
are scalar-prefetch operands and the BlockSpec index map streams exactly
the physical (bs, Hkv*hd) tile the slot's table names; masking stays
purely positional (unbound entry / beyond ``t`` / outside the window).
GQA is a static loop over kv heads, each folding its (group, bs) score
tile into per-head online-softmax statistics.

Oracle: ``repro.kernels.ref.fused_decode_tail``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, t_ref, q_ref, k_ref, v_ref, wo_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, window, bs, ne, hkv, hd):
    ib = pl.program_id(0)
    e = pl.program_id(1)
    group = wo_ref.shape[0] // (hd * hkv)

    @pl.when(e == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = tables_ref[ib, e]                              # physical block id
    t = t_ref[ib]
    pos = e * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = (blk >= 0) & (pos <= t)                       # (1, bs)
    if window > 0:
        mask &= pos > t - window

    # static loop over kv heads: each folds its (group, bs) score tile
    # into the per-q-head online-softmax running statistics.  Head kh's
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
        # contexts are still in VMEM: fold the output projection in
        # before anything round-trips through HBM — one (1, hd) x (hd, D)
        # product per query head h = kh*group + j, over wo's rows
        # [h*hd, (h+1)*hd).
        o = jnp.zeros((1, o_ref.shape[1]), jnp.float32)  # (1, D)
        for kh in range(hkv):
            for j in range(group):
                h = kh * group + j
                ctx = acc_ref[kh, pl.ds(j, 1), :] / jnp.maximum(
                    l_ref[kh, pl.ds(j, 1), :], 1e-30)    # (1, hd)
                o += jax.lax.dot_general(
                    ctx, wo_ref[h * hd:(h + 1) * hd, :].astype(jnp.float32),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        o_ref[...] = jnp.broadcast_to(o, o_ref.shape)


def fused_decode_tail_pallas(q, k_pool, v_pool, wo, block_tables, t, *,
                             window=0, softmax_scale=None, interpret=False):
    """q: (B, H, hd); pools: (N, bs, Hkv, hd); wo: (H*hd, D);
    block_tables: (B, E) int32 (-1 = unbound); t: (B,) int32 current
    absolute position.  Returns (B, D).  hd and D must be multiples of
    128 (``ops`` pads them)."""
    b, h, hd = q.shape
    n, bs, hkv, _ = k_pool.shape
    e = block_tables.shape[1]
    d = wo.shape[1]
    g = h // hkv
    gp = -(-g // 8) * 8
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    # the same tile-aligned layouts as paged_decode_attention; the (1, D)
    # result of a slot is written as a whole (8, D) f32 tile, row 0 kept.
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
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # block_tables, t
        grid=(b, e),
        in_specs=[
            pl.BlockSpec((None, hkv, gp, hd),
                         lambda b_, e_, bt, tt: (b_, 0, 0, 0)),
            kv_spec, kv_spec,
            pl.BlockSpec((h * hd, d), lambda b_, e_, bt, tt: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 8, d), lambda b_, e_, bt, tt: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, gp, hd), jnp.float32),
            pltpu.VMEM((hkv, gp, 1), jnp.float32),
            pltpu.VMEM((hkv, gp, 1), jnp.float32),
        ],
    )
    # wo is resident for the whole grid and double-buffered by the
    # pipeline: at 1536 x 1536 in f32 that alone is 18.9 MB, above the
    # default scoped-VMEM limit, so the limit is sized from the blocks.
    blocks = (h * hd * d * jnp.dtype(wo.dtype).itemsize
              + 2 * bs * hkv * hd * jnp.dtype(k_pool.dtype).itemsize
              + (hkv * gp * hd + 8 * d) * 4)
    vmem = min(2 * blocks + (8 << 20), 100 << 20)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, bs=bs,
                          ne=e, hkv=hkv, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 8, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem, 32 << 20)),
        interpret=interpret,
        name="fused_decode_tail",
    )(block_tables.astype(jnp.int32), t.astype(jnp.int32), q4, kf, vf, wo)
    return out[:, 0].astype(q.dtype)
