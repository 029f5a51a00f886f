"""Pallas TPU flash attention (training / prefill path).

TPU-native tiling: the grid iterates (batch, q-head, q-block, k-block)
with the k-block axis minor-most and sequential, so the online-softmax
running statistics live in VMEM scratch across k iterations.  Blocks are
128-aligned for the MXU, and heads sit side by side on the lane axis so
each (block, hd) block is a whole TPU tile; GQA is
expressed in the k/v BlockSpec index maps (q-head h reads kv-head
h // group).  There is no reverse-mode rule: the trainer differentiates
the jnp oracle (``repro.core.trainer.TRAIN_ATTENTION``).

Supports: causal masking, sliding windows, packed-sequence segment ids.
Oracle: ``repro.kernels.ref.flash_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, causal, window, bq, bk, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[...].astype(jnp.float32)                 # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = sq_ref[...][:, :1] == sk_ref[...][:1, :]     # (bq, 1) vs (1, bk)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)        # guard all-masked rows
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, segment_ids=None, *, causal=True,
                           window=0, softmax_scale=None,
                           block_q=128, block_k=128, interpret=False):
    """q: (B, S, H, hd); k, v: (B, S, Hkv, hd); segment_ids: (B, S).
    hd must be a multiple of 128 and each block either a multiple of 128
    or all of S (``ops`` pads and picks them)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    group = h // hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5

    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, "caller pads S"
    nq, nk = s // block_q, s // block_k
    if segment_ids is None:
        segment_ids = jnp.zeros((b, s), jnp.int32)
    # Tile-aligned layouts: heads side by side on the lane axis (a free
    # reshape; head h is the lane-aligned column block [h*hd, (h+1)*hd)),
    # query segments broadcast across 128 lanes and key segments across 8
    # sublanes, so each segment block is a whole tile; the kernel compares
    # a column of one with a row of the other.
    qf = q.reshape(b, s, h * hd)
    kf = k.reshape(b, s, hkv * hd)
    vf = v.reshape(b, s, hkv * hd)
    seg = segment_ids.astype(jnp.int32)
    seg_q = jnp.broadcast_to(seg[:, :, None], (b, s, 128))
    seg_k = jnp.broadcast_to(seg[:, None, :], (b, 8, s))

    q_spec = pl.BlockSpec((None, block_q, hd),
                          lambda b_, h_, iq, ik: (b_, iq, h_))
    kv_spec = pl.BlockSpec((None, block_k, hd),
                           lambda b_, h_, iq, ik, g=group: (b_, ik, h_ // g))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=block_q, bk=block_k, nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((None, block_q, 128), lambda b_, h_, iq, ik: (b_, iq, 0)),
            pl.BlockSpec((None, 8, block_k), lambda b_, h_, iq, ik: (b_, 0, ik)),
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf, seg_q, seg_k)
    return out.reshape(b, s, h, hd)
