"""Pure-jnp oracles for every Pallas kernel.

These are the semantics of record: the Pallas kernels must match them
(tests sweep shapes/dtypes and assert_allclose), and they are also the
execution path on every platform but the TPU (DESIGN.md §Kernel backend:
Pallas TPU kernels do not lower on the CPU backend; tests validate the
kernel bodies with ``backend="pallas_interpret"``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_expand(k, n_heads):
    """(B, S, Hkv, hd) -> (B, S, H, hd) by repeating kv heads."""
    b, s, hkv, hd = k.shape
    group = n_heads // hkv
    return jnp.repeat(k, group, axis=2) if group > 1 else k


def flash_attention(q, k, v, *, segment_ids=None, causal: bool = True,
                    window: int = 0, softmax_scale: Optional[float] = None):
    """Masked multi-head attention over a full sequence.

    q: (B, Sq, H, hd); k, v: (B, Sk, Hkv, hd) with H % Hkv == 0 (Sq != Sk
    supported for cross attention).  segment_ids: (B, S) int32 (or a
    (seg_q, seg_kv) tuple) — packed sequences; tokens attend only within
    their segment.  window > 0 -> sliding-window attention (token t sees
    keys in (t-window, t]).  Returns (B, Sq, H, hd).
    """
    b, s, h, hd = q.shape
    sk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    kx = _gqa_expand(k, h)
    vx = _gqa_expand(v, h)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((s, sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window and window > 0:
        mask &= (qpos - kpos) < window
    mask = mask[None, None]
    if segment_ids is not None:
        seg_q, seg_kv = (segment_ids if isinstance(segment_ids, tuple)
                         else (segment_ids, segment_ids))
        segmask = seg_q[:, None, :, None] == seg_kv[:, None, None, :]
        mask = mask & segmask
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vx.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention_chunked(q, k, v, segment_ids=None, *, causal: bool = True,
                            window: int = 0, softmax_scale=None,
                            chunk: int = 128):
    """Memory-bounded attention: scan over query chunks, full-row softmax
    per chunk, grouped-GQA einsums (kv never expanded).  O(B*H*chunk*Sk)
    temporaries instead of O(B*H*Sq*Sk) — the pure-jnp flash pattern used
    for long sequences (the Pallas kernel is the TPU-native version; this
    path is what the dry-run lowers).  The chunk body is rematerialized in
    the backward pass, exactly like a flash-attention backward.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    chunk = min(chunk, sq)
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // chunk
    qs = q.reshape(b, nq, chunk, h, hd).transpose(1, 0, 2, 3, 4)

    seg_q, seg_kv = (None, None)
    if segment_ids is not None:
        seg_q, seg_kv = (segment_ids if isinstance(segment_ids, tuple)
                         else (segment_ids, segment_ids))
        sq_p = jnp.pad(seg_q, ((0, 0), (0, pad)), constant_values=-1) if pad else seg_q
        sq_chunks = sq_p.reshape(b, nq, chunk).transpose(1, 0, 2)
    kpos = jnp.arange(sk)

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def body(carry, xs):
        if segment_ids is not None:
            qc, idx, segc = xs
        else:
            qc, idx = xs
            segc = None
        qg = qc.reshape(b, chunk, hkv, g, hd).astype(jnp.float32)
        s = jnp.einsum("bqngd,bknd->bngqk", qg, kf) * scale
        # s: (b, hkv, g, chunk, sk)
        qpos = idx * chunk + jnp.arange(chunk)
        mask = jnp.ones((chunk, sk), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window and window > 0:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        mask = mask[None, None, None]
        if segc is not None:
            mask = mask & (segc[:, None, None, :, None] == seg_kv[:, None, None, None, :])
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(mask, jnp.exp(s - m), 0.0)
        den = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("bngqk,bknd->bqngd", p / den, vf)
        return carry, o.reshape(b, chunk, h, hd)

    body = jax.checkpoint(body)
    xs = (qs, jnp.arange(nq), sq_chunks) if segment_ids is not None \
        else (qs, jnp.arange(nq))
    _, outs = jax.lax.scan(body, (), xs)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * chunk, h, hd)
    return out[:, :sq].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_pos, t, *, window: int = 0,
                     softmax_scale: Optional[float] = None):
    """Single-token attention against a ring-buffer KV cache.

    q: (B, H, hd) — the current token's query (at absolute position t).
    k_cache, v_cache: (B, W, Hkv, hd); cache_pos: (B, W) int32 absolute
    positions of each slot, -1 for empty.  t: (B,) int32 current position.
    window > 0 masks positions <= t - window.  Returns (B, H, hd).
    """
    b, h, hd = q.shape
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    hkv = k_cache.shape[2]
    group = h // hkv
    qg = q.reshape(b, hkv, group, hd)
    # NOTE: no .astype(f32) on the caches — that would stream a full-cache
    # f32 copy through HBM every decode step; f32 accumulation happens
    # inside the einsum (preferred_element_type), matching the Pallas
    # kernel's bf16-tiles/f32-accumulate behaviour.
    scores = jnp.einsum("bngd,bwnd->bngw", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    tb = t.reshape(b, 1, 1, 1).astype(jnp.int32)
    pos = cache_pos[:, None, None, :]
    valid = (pos >= 0) & (pos <= tb)
    if window and window > 0:
        valid &= pos > tb - window
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngw,bwnd->bngd", probs.astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, hd).astype(q.dtype)


def chunked_prefill_attention(q, k, v, key_pos, q_pos, *, window: int = 0,
                              softmax_scale: Optional[float] = None):
    """Chunk-of-queries attention against positioned keys (the prefill
    continuation primitive, DESIGN.md §Chunked prefill).

    q: (B, C, H, hd) — C query tokens at absolute positions q_pos (B, C)
    (-1 = padded query row; its output is unspecified and must be
    discarded).  k, v: (B, S, Hkv, hd) with key_pos (B, S) absolute
    positions, -1 = invalid entry.  A key is visible to a query iff
    key_pos >= 0, key_pos <= q_pos (causality), and — for window > 0 —
    q_pos - key_pos < window.  Generalizes ``decode_attention``: with
    C = 1 and q_pos = t it is the same computation.
    """
    b, c, h, hd = q.shape
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    hkv = k.shape[2]
    group = h // hkv
    qg = q.reshape(b, c, hkv, group, hd)
    scores = jnp.einsum("bcngd,bwnd->bcngw", qg, k,
                        preferred_element_type=jnp.float32) * scale
    qp = q_pos[:, :, None, None, None].astype(jnp.int32)
    kp = key_pos[:, None, None, None, :].astype(jnp.int32)
    valid = (kp >= 0) & (kp <= qp) & (qp >= 0)
    if window and window > 0:
        valid &= kp > qp - window
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bcngw,bwnd->bcngd", probs.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, h, hd).astype(q.dtype)


def paged_prefill_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                            window: int = 0,
                            softmax_scale: Optional[float] = None):
    """Chunk-of-queries attention against a paged KV-block pool (the
    paged prefill continuation, DESIGN.md §Chunked prefill).

    q: (B, C, H, hd) — a chunk of C query tokens at absolute positions
    q_pos (B, C) (-1 = padded row).  k_pool, v_pool: (N, bs, Hkv, hd);
    block_tables: (B, E) int32, entry e covering positions
    [e*bs, (e+1)*bs), -1 = unbound.  The chunk's own K/V must already be
    written to the pool (write-then-read; blocks never wrap, unlike the
    ring cache).  Semantics of record: gather each slot's blocks into a
    flat positioned cache — exactly as ``paged_decode_attention`` does —
    and defer to ``chunked_prefill_attention``.
    """
    b = q.shape[0]
    n, bs, hkv, hd = k_pool.shape
    e = block_tables.shape[1]
    safe = jnp.clip(block_tables, 0, n - 1)                 # (B, E)
    kg = k_pool[safe].reshape(b, e * bs, hkv, hd)
    vg = v_pool[safe].reshape(b, e * bs, hkv, hd)
    pos = jnp.broadcast_to(jnp.arange(e * bs, dtype=jnp.int32)[None], (b, e * bs))
    bound = jnp.repeat(block_tables >= 0, bs, axis=1)       # (B, E*bs)
    key_pos = jnp.where(bound, pos, -1)
    return chunked_prefill_attention(q, kg, vg, key_pos, q_pos,
                                     window=window, softmax_scale=softmax_scale)


def paged_decode_attention(q, k_pool, v_pool, block_tables, t, *,
                           window: int = 0,
                           softmax_scale: Optional[float] = None):
    """Single-token attention against a paged KV-block pool.

    q: (B, H, hd) — the current token's query (at absolute position t).
    k_pool, v_pool: (N, bs, Hkv, hd) — the global pool of N fixed-size
    KV blocks shared by every slot (DESIGN.md §Paged KV-cache pool).
    block_tables: (B, E) int32 — per-slot logical->physical block map;
    entry e covers absolute positions [e*bs, (e+1)*bs); -1 = unbound.
    t: (B,) int32 current position.  window > 0 masks positions
    <= t - window.  Returns (B, H, hd).

    Semantics of record: gather each slot's blocks into a flat (B, E*bs)
    cache with explicit positions and defer to ``decode_attention`` —
    positional masking makes partial last blocks, unbound entries, and
    sliding windows fall out of the same rule.
    """
    b = q.shape[0]
    n, bs, hkv, hd = k_pool.shape
    e = block_tables.shape[1]
    safe = jnp.clip(block_tables, 0, n - 1)                 # (B, E)
    kg = k_pool[safe].reshape(b, e * bs, hkv, hd)
    vg = v_pool[safe].reshape(b, e * bs, hkv, hd)
    pos = jnp.broadcast_to(jnp.arange(e * bs, dtype=jnp.int32)[None], (b, e * bs))
    bound = jnp.repeat(block_tables >= 0, bs, axis=1)       # (B, E*bs)
    cache_pos = jnp.where(bound, pos, -1)
    return decode_attention(q, kg, vg, cache_pos, t, window=window,
                            softmax_scale=softmax_scale)


def fused_decode_tail(q, k_pool, v_pool, wo, block_tables, t, *,
                      window: int = 0,
                      softmax_scale: Optional[float] = None):
    """Paged decode attention fused with the output projection (the
    decode-tail fusion, DESIGN.md §Fused decode tail).

    q: (B, H, hd); k_pool, v_pool: (N, bs, Hkv, hd); wo: (H*hd, D) — the
    attention output projection.  block_tables: (B, E) int32, t: (B,)
    int32, exactly as in ``paged_decode_attention``.  Returns (B, D).

    Semantics of record: the composition of ``paged_decode_attention``
    and the projection matmul, in the same op order as the unfused model
    path — so the fused engine mode is bitwise-identical to the default
    path on the jnp backend, and the Pallas kernel's single-pass
    gather+softmax+projection is validated against this composition.
    """
    b, h, hd = q.shape
    out = paged_decode_attention(q, k_pool, v_pool, block_tables, t,
                                 window=window, softmax_scale=softmax_scale)
    return jnp.matmul(out.reshape(b, h * hd), wo,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def linear_scan(a, x, h0=None):
    """Diagonal linear recurrence  h_t = a_t * h_{t-1} + x_t.

    a, x: (B, S, C); h0: (B, C) initial state (zeros if None).
    Returns (h (B, S, C), h_last (B, C)).  This is the RG-LRU / gated
    linear-attention primitive; computed with an associative scan.
    """
    af = a.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    if h0 is not None:
        # fold h0 into the first step: h_1 = a_1 h_0 + x_1
        xf = xf.at[:, 0, :].add(af[:, 0, :] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, x1 = c1
        a2, x2 = c2
        return a2 * a1, a2 * x1 + x2

    a_c, h = jax.lax.associative_scan(combine, (af, xf), axis=1)
    return h.astype(x.dtype), h[:, -1, :].astype(x.dtype)
