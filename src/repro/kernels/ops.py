"""Jit-friendly kernel entry points with backend dispatch + padding.

Backends (DESIGN.md §Kernel backend):
  * ``"pallas"``            compiled Pallas — what runs on a TPU
  * ``"jnp"``               the pure-jnp oracles of ``ref.py`` — what
                            runs on every other platform
  * ``"pallas_interpret"``  Pallas kernel bodies executed by the
                            interpreter; only tests ask for it, per call

The backend is a platform rule, decided when a call is traced:
``platform_backend`` gives ``"pallas"`` when JAX's default backend is a
TPU and ``"jnp"`` otherwise.  Code that must trace one backend on
purpose scopes it with ``use_backend``: the trainer's differentiated
forward (``repro.core.trainer.TRAIN_ATTENTION``, since the Pallas
kernels have no reverse-mode rule), and compile checks that target a
described TPU from a CPU host.  Two jnp branches are decisions, not
fallbacks: cross-attention (Sq != Sk) and ring-cache prefill
continuation are not on the TPU serving path (the paged engine is).

The wrappers pad sequence/cache/channel dims to hardware-aligned block
multiples (and head_dim to a lane multiple of 128) before calling the
Pallas kernels, then slice back — callers never see alignment.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_decode_tail import fused_decode_tail_pallas
from repro.kernels.linear_scan import linear_scan_pallas
from repro.kernels.paged_decode_attention import paged_decode_attention_pallas
from repro.kernels.paged_prefill_attention import paged_prefill_attention_pallas

BACKENDS = ("jnp", "pallas", "pallas_interpret")
_LANE = 128
# rows per block: a whole (16, 128) tile of a 16-bit dtype (two f32 tiles)
_SUBLANE = 16
# switch to the q-chunked flash pattern when the full score matrix would
# exceed ~ (1024 x 1024) per (batch, head) — keeps dry-run memory sane
_CHUNKED_THRESHOLD = 1024 * 1024
_SCOPED: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "kernel_backend", default=None)


def platform_backend() -> str:
    """The platform rule: compiled Pallas on a TPU, jnp oracles elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


@contextlib.contextmanager
def use_backend(backend: str) -> Iterator[None]:
    """Trace the calls made inside this scope (this thread) with
    ``backend`` ("jnp" or "pallas").  A jitted function keeps what it was
    traced with, so the scope must enclose its first call or sit inside
    its body."""
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"use_backend takes 'jnp' or 'pallas', not {backend!r}")
    token = _SCOPED.set(backend)
    try:
        yield
    finally:
        _SCOPED.reset(token)


def current_backend() -> str:
    """The backend a call traced here gets: the scoped one, else the
    platform's."""
    return _SCOPED.get() or platform_backend()


def _resolve(backend: Optional[str]) -> str:
    backend = backend or current_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}")
    return backend


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_axis(x, axis: int, target: int, value=0):
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, pad)
    return jnp.pad(x, pads, constant_values=value)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, segment_ids=None, *, causal: bool = True,
                    window: int = 0, softmax_scale: Optional[float] = None,
                    backend: Optional[str] = None):
    backend = _resolve(backend)
    if backend == "jnp" or q.shape[1] != k.shape[1]:
        # cross-attention (Sq != Sk) stays on the jnp path
        if q.shape[1] * k.shape[1] > _CHUNKED_THRESHOLD:
            return _ref.flash_attention_chunked(
                q, k, v, segment_ids=segment_ids, causal=causal,
                window=window, softmax_scale=softmax_scale)
        return _ref.flash_attention(q, k, v, segment_ids=segment_ids,
                                    causal=causal, window=window,
                                    softmax_scale=softmax_scale)
    b, s, h, hd = q.shape
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    bq = bk = min(128, _round_up(s, _SUBLANE))
    sp = _round_up(s, max(bq, bk))
    hdp = _round_up(hd, _LANE)
    if segment_ids is None:
        segment_ids = jnp.zeros((b, s), jnp.int32)
    qp = _pad_axis(_pad_axis(q, 1, sp), 3, hdp)
    kp = _pad_axis(_pad_axis(k, 1, sp), 3, hdp)
    vp = _pad_axis(_pad_axis(v, 1, sp), 3, hdp)
    seg = _pad_axis(segment_ids, 1, sp, value=-1)   # padded keys never match
    out = flash_attention_pallas(qp, kp, vp, seg, causal=causal, window=window,
                                 softmax_scale=scale, block_q=bq, block_k=bk,
                                 interpret=(backend == "pallas_interpret"))
    return out[:, :s, :, :hd]


# ---------------------------------------------------------------------------
# decode attention (ring-buffer KV cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, cache_pos, t, *, window: int = 0,
                     softmax_scale: Optional[float] = None,
                     backend: Optional[str] = None):
    backend = _resolve(backend)
    if backend == "jnp":
        return _ref.decode_attention(q, k_cache, v_cache, cache_pos, t,
                                     window=window, softmax_scale=softmax_scale)
    b, h, hd = q.shape
    w = k_cache.shape[1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    bw = min(256, _round_up(w, _SUBLANE))
    wp = _round_up(w, bw)
    hdp = _round_up(hd, _LANE)
    qp = _pad_axis(q, 2, hdp)
    kp = _pad_axis(_pad_axis(k_cache, 1, wp), 3, hdp)
    vp = _pad_axis(_pad_axis(v_cache, 1, wp), 3, hdp)
    pos = _pad_axis(cache_pos, 1, wp, value=-1)
    out = decode_attention_pallas(qp, kp, vp, pos, t, window=window,
                                  softmax_scale=scale, block_w=bw,
                                  interpret=(backend == "pallas_interpret"))
    return out[:, :, :hd]


# ---------------------------------------------------------------------------
# paged decode attention (block-table KV pool)
# ---------------------------------------------------------------------------

def paged_decode_attention(q, k_pool, v_pool, block_tables, t, *,
                           window: int = 0,
                           softmax_scale: Optional[float] = None,
                           backend: Optional[str] = None):
    """q: (B, H, hd); pools: (N, bs, Hkv, hd); block_tables: (B, E) int32
    (-1 = unbound); t: (B,) int32.  See DESIGN.md §Paged KV-cache pool."""
    backend = _resolve(backend)
    if backend == "jnp":
        return _ref.paged_decode_attention(q, k_pool, v_pool, block_tables, t,
                                           window=window,
                                           softmax_scale=softmax_scale)
    b, h, hd = q.shape
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    hdp = _round_up(hd, _LANE)
    qp = _pad_axis(q, 2, hdp)
    kp = _pad_axis(k_pool, 3, hdp)
    vp = _pad_axis(v_pool, 3, hdp)
    out = paged_decode_attention_pallas(
        qp, kp, vp, block_tables, t, window=window, softmax_scale=scale,
        interpret=(backend == "pallas_interpret"))
    return out[:, :, :hd]


# ---------------------------------------------------------------------------
# fused decode tail (DESIGN.md §Fused decode tail)
# ---------------------------------------------------------------------------

def fused_decode_tail(q, k_pool, v_pool, wo, block_tables, t, *,
                      window: int = 0,
                      softmax_scale: Optional[float] = None,
                      backend: Optional[str] = None):
    """Paged decode attention fused with the output projection: q (B, H,
    hd) against pools (N, bs, Hkv, hd) through block_tables (B, E),
    projected by wo (H*hd, D) in the same kernel — returns (B, D), never
    materializing the (B, H, hd) contexts (DESIGN.md §Fused decode
    tail).  wo is padded per head (the pad rows multiply the padded
    context columns, which are zero)."""
    backend = _resolve(backend)
    if backend == "jnp":
        return _ref.fused_decode_tail(q, k_pool, v_pool, wo, block_tables, t,
                                      window=window,
                                      softmax_scale=softmax_scale)
    b, h, hd = q.shape
    d = wo.shape[1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    hdp = _round_up(hd, _LANE)
    dp = _round_up(d, _LANE)
    qp = _pad_axis(q, 2, hdp)
    kp = _pad_axis(k_pool, 3, hdp)
    vp = _pad_axis(v_pool, 3, hdp)
    # per-head padding: (H*hd, D) -> (H, hd, D) -> pad hd and D -> flat
    wop = _pad_axis(_pad_axis(wo.reshape(h, hd, d), 1, hdp), 2, dp)
    wop = wop.reshape(h * hdp, dp)
    out = fused_decode_tail_pallas(
        qp, kp, vp, wop, block_tables, t, window=window, softmax_scale=scale,
        interpret=(backend == "pallas_interpret"))
    return out[:, :d]


# ---------------------------------------------------------------------------
# prefill continuation (chunked prefill, DESIGN.md §Chunked prefill)
# ---------------------------------------------------------------------------

def chunked_prefill_attention(q, k, v, key_pos, q_pos, *, window: int = 0,
                              softmax_scale: Optional[float] = None,
                              backend: Optional[str] = None):
    """q: (B, C, H, hd) chunk of queries at absolute positions q_pos
    (B, C); k, v: (B, S, Hkv, hd) with key_pos (B, S) absolute positions
    (-1 = invalid).  Ring-cache prefill continuation: like
    cross-attention in ``flash_attention``, this stays on the jnp oracle
    on every backend — the production TPU path is the paged engine,
    whose continuation has the Pallas kernel below."""
    del backend
    return _ref.chunked_prefill_attention(q, k, v, key_pos, q_pos,
                                          window=window,
                                          softmax_scale=softmax_scale)


def paged_prefill_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                            window: int = 0,
                            softmax_scale: Optional[float] = None,
                            backend: Optional[str] = None):
    """q: (B, C, H, hd) chunk of queries at absolute positions q_pos
    (B, C) (-1 = padded row); pools: (N, bs, Hkv, hd); block_tables:
    (B, E) int32 (-1 = unbound).  The chunk's own K/V must already be in
    the pool (write-then-read).  See DESIGN.md §Chunked prefill."""
    backend = _resolve(backend)
    if backend == "jnp":
        return _ref.paged_prefill_attention(q, k_pool, v_pool, block_tables,
                                            q_pos, window=window,
                                            softmax_scale=softmax_scale)
    b, c, h, hd = q.shape
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    cp = _round_up(c, _SUBLANE)
    hdp = _round_up(hd, _LANE)
    qp = _pad_axis(_pad_axis(q, 1, cp), 3, hdp)
    kp = _pad_axis(k_pool, 3, hdp)
    vp = _pad_axis(v_pool, 3, hdp)
    qpos = _pad_axis(q_pos, 1, cp, value=-1)    # padded queries mask out
    out = paged_prefill_attention_pallas(
        qp, kp, vp, block_tables, qpos, window=window, softmax_scale=scale,
        interpret=(backend == "pallas_interpret"))
    return out[:, :c, :, :hd]


# ---------------------------------------------------------------------------
# diagonal linear scan
# ---------------------------------------------------------------------------

def linear_scan(a, x, h0=None, *, backend: Optional[str] = None):
    backend = _resolve(backend)
    if backend == "jnp":
        return _ref.linear_scan(a, x, h0)
    b, s, c = a.shape
    bt = min(256, _round_up(s, _SUBLANE))
    bc = min(256, _round_up(c, _LANE))
    sp, cp = _round_up(s, bt), _round_up(c, bc)
    ap = _pad_axis(_pad_axis(a, 1, sp), 2, cp)       # padded a=0 keeps carry math finite
    xp = _pad_axis(_pad_axis(x, 1, sp), 2, cp)
    h0p = None if h0 is None else _pad_axis(h0, 1, cp)
    h, h_last = linear_scan_pallas(ap, xp, h0p,
                                   block_t=bt, block_c=bc,
                                   interpret=(backend == "pallas_interpret"))
    # h_last must come from the true last step, not the padded tail
    return h[:, :s, :c], h[:, s - 1, :c]
