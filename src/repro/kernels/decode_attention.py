"""Pallas TPU flash-decoding: one query token vs. a long ring-buffer KV cache.

This is the rollout-worker hot spot in AReaL (autoregressive decoding
dominates generation time).  The grid iterates (batch, kv-block) with
the kv-block axis sequential; each step streams one (block_w, Hkv*hd)
cache tile HBM->VMEM and folds it into every query head's online-softmax
running statistics (a static loop over kv heads).
Ring-buffer semantics: each slot carries its absolute position (-1 =
empty), so masking (validity, causality, sliding window) is positional
and wrap-around needs no special casing.

Oracle: ``repro.kernels.ref.decode_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(t_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, window, nw, hkv, hd):
    ib = pl.program_id(0)
    iw = pl.program_id(1)

    @pl.when(iw == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[...][:1, :]                            # (1, bw)
    t = t_ref[ib]
    mask = (pos >= 0) & (pos <= t)
    if window > 0:
        mask &= pos > t - window

    # static loop over kv heads: head kh's K/V are the lane-aligned
    # columns [kh*hd, (kh+1)*hd) of the tile.
    for kh in range(hkv):
        q = q_ref[kh].astype(jnp.float32)                # (g, hd)
        k = k_ref[:, kh * hd:(kh + 1) * hd].astype(jnp.float32)   # (bw, hd)
        v = v_ref[:, kh * hd:(kh + 1) * hd].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (g, bw)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[kh]                               # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)     # (g, bw)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[kh] = l_ref[kh] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[kh] = acc_ref[kh] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[kh] = m_new

    @pl.when(iw == nw - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, cache_pos, t, *, window=0,
                            softmax_scale=None, block_w=256, interpret=False):
    """q: (B, H, hd); caches: (B, W, Hkv, hd); cache_pos: (B, W); t: (B,).
    hd must be a multiple of 128, and block_w a multiple of 128 or all
    of W, itself a multiple of 16 (``ops`` pads and picks them)."""
    b, h, hd = q.shape
    w = k_cache.shape[1]
    hkv = k_cache.shape[2]
    g = h // hkv
    gp = -(-g // 8) * 8
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    block_w = min(block_w, w)
    assert w % block_w == 0, "caller pads W"
    nw = w // block_w
    # Tile-aligned layouts: q head h = kh*g + j becomes (kh, j), the group
    # padded to 8 rows in f32; a cache row's heads sit side by side on
    # the lane axis (a free reshape); positions broadcast across 8
    # sublanes, of which the kernel reads row 0.
    q4 = jnp.pad(q.astype(jnp.float32).reshape(b, hkv, g, hd),
                 ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    kf = k_cache.reshape(b, w, hkv * hd)
    vf = v_cache.reshape(b, w, hkv * hd)
    pos = jnp.broadcast_to(cache_pos.astype(jnp.int32)[:, None, :], (b, 8, w))

    q_spec = pl.BlockSpec((None, hkv, gp, hd), lambda b_, iw, tt: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, block_w, hkv * hd),
                           lambda b_, iw, tt: (b_, iw, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                  # t
        grid=(b, nw),
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((None, 8, block_w),
                               lambda b_, iw, tt: (b_, 0, iw))],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, gp, hd), jnp.float32),
            pltpu.VMEM((hkv, gp, 1), jnp.float32),
            pltpu.VMEM((hkv, gp, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, nw=nw,
                          hkv=hkv, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q4.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(t.astype(jnp.int32), q4, kf, vf, pos)
    return out[:, :, :g].reshape(b, h, hd).astype(q.dtype)
