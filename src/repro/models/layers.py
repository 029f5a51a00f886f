"""Shared neural-net building blocks (pure functions over param pytrees).

No flax/haiku dependency: parameters are nested dicts of jnp arrays,
initialized by explicit ``*_init`` functions and consumed by ``*_apply``
functions.  All matmuls accumulate in fp32 (``preferred_element_type``)
so bf16 params are safe on TPU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def matmul(x, w):
    # NOTE (§Perf, refuted hypothesis): a custom-vjp keeping backward dot
    # operands in bf16 did NOT shrink the f32 weight all-gathers seen in
    # the dry-run HLO — those converts come from the CPU backend's
    # f32-dot lowering (pre-SPMD), not from autodiff promotion; on TPU
    # the gathers are bf16.  Collective bytes for bf16 programs in the
    # CPU dry-run are therefore a <=2x upper bound.
    if _narrower(w, x):
        # a run weight (DESIGN.md §Rollout run weights): meet it in its
        # dtype, or JAX promotes it back and XLA converts it again
        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def _narrower(w, x) -> bool:
    return jnp.dtype(w.dtype).itemsize < jnp.dtype(x.dtype).itemsize


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, dim: int, dtype=jnp.float32):
    if not cfg.parametric_norm:
        return {}
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((dim,), dtype)}
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def norm_apply(cfg: ModelConfig, params, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    if params:
        xf = xf * params["scale"].astype(jnp.float32)
        if "bias" in params:
            xf = xf + params["bias"].astype(jnp.float32)
    return xf.astype(x.dtype)


def head_norm_init(dim: int, dtype=jnp.float32):
    """QK-norm (per-head RMS norm) scale."""
    return {"scale": jnp.ones((dim,), dtype)}


def head_norm_apply(params, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    if theta <= 0:           # arch without RoPE (whisper uses learned pos)
        return x
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                          # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@functools.lru_cache(maxsize=8)
def sinusoidal_positions(n_pos: int, dim: int):
    """Whisper-style sinusoidal embedding table (n_pos, dim).

    Cached at module level: the table is a pure function of static
    shape arguments, but it used to be rebuilt on EVERY trace of the
    decode/prefill paths of rope_theta<=0 architectures — each jit
    signature paid the (n_pos, dim) host build again.  The lru_cache
    makes every trace capture the same constant (one device buffer)."""
    log_ts = math.log(10_000.0) / (dim // 2 - 1)
    inv = jnp.exp(-log_ts * jnp.arange(dim // 2, dtype=jnp.float32))
    ang = jnp.arange(n_pos, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / GeLU / squared-ReLU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None, dtype=jnp.float32):
    ff = d_ff or cfg.d_ff
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d, ff, dtype),
         "w_down": dense_init(ks[1], ff, d, dtype)}
    if cfg.act in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[2], d, ff, dtype)
    return p


def mlp_apply(cfg: ModelConfig, params, x):
    h = matmul(x, params["w_up"])
    if cfg.act == "swiglu":
        g = matmul(x, params["w_gate"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    elif cfg.act == "geglu":
        g = matmul(x, params["w_gate"])
        h = jax.nn.gelu(g.astype(jnp.float32)).astype(x.dtype) * h
    elif cfg.act == "gelu":
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    elif cfg.act == "relu2":
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
    else:
        raise ValueError(cfg.act)
    return matmul(h, params["w_down"])


# ---------------------------------------------------------------------------
# Causal temporal conv (recurrent blocks)
# ---------------------------------------------------------------------------

def causal_conv1d_init(key, width: int, channels: int, dtype=jnp.float32):
    return {"w": (jax.random.normal(key, (width, channels), jnp.float32)
                  / math.sqrt(width)).astype(dtype)}


def causal_conv1d_apply(params, x, segment_ids=None, history=None):
    """Depthwise causal conv.  x: (B, S, C).  With segment_ids, taps that
    reach across a packed-segment boundary are zeroed (no leakage).
    ``history`` (B, W-1, C) replaces the zero left-pad with the last real
    inputs of an earlier span — the chunked-prefill continuation
    (DESIGN.md §Chunked prefill); mutually exclusive with segment_ids."""
    w = params["w"]                       # (W, C)
    width = w.shape[0]
    s = x.shape[1]
    if history is not None:
        assert segment_ids is None, "conv history and packing are exclusive"
        xp = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    else:
        xp = jnp.pad(x, [(0, 0), (width - 1, 0), (0, 0)])
    if segment_ids is not None:
        sp = jnp.pad(segment_ids, [(0, 0), (width - 1, 0)],
                     constant_values=-2)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):
        tap = xp[:, i:i + s, :].astype(jnp.float32)
        if segment_ids is not None:
            ok = (sp[:, i:i + s] == segment_ids)[..., None]
            tap = jnp.where(ok, tap, 0.0)
        out = out + tap * w[i].astype(jnp.float32)
    return out.astype(x.dtype)


def conv_history_update(history, x, length):
    """Roll a (B, W-1, C) conv history forward over a right-padded span.

    x: (B, S, C) span inputs with ``length`` (B,) real rows each; returns
    the last W-1 *real* inputs of history ++ x — the state a stepwise
    decode would have left (DESIGN.md §Chunked prefill)."""
    w = history.shape[1]
    cat = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    # real content occupies cat[:, :w + length); its last w rows start at
    # ``length`` (always >= 0, so no clipping of the window start)
    idx = length[:, None] + jnp.arange(w)[None, :]                 # (B, w)
    return jnp.take_along_axis(
        cat, jnp.clip(idx, 0, cat.shape[1] - 1)[..., None], axis=1)


def causal_conv1d_step(params, conv_state, x_t):
    """One decode step.  conv_state: (B, W-1, C) previous inputs; x_t: (B, C)."""
    w = params["w"]
    hist = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B, W, C)
    out = jnp.sum(hist.astype(jnp.float32) * w[None].astype(jnp.float32), axis=1)
    return hist[:, 1:, :], out.astype(x_t.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig, dtype=jnp.float32):
    table = (jax.random.normal(key, (cfg.padded_vocab, cfg.d_model), jnp.float32)
             * 0.02).astype(dtype)
    return {"table": table}


def embed_apply(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed_apply(params_embed, params_head, x, tie: bool):
    if tie:
        w = params_embed["table"].T
    else:
        w = params_head["w"]
    if _narrower(w, x):
        x = x.astype(w.dtype)              # as in ``matmul``
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)
