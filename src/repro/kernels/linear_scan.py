"""Pallas TPU blocked diagonal linear scan:  h_t = a_t * h_{t-1} + x_t.

The RG-LRU / gated-linear-recurrence primitive (RecurrentGemma blocks,
xLSTM prefix re-scan after an AReaL weight-update interruption).  The
grid iterates (batch, channel-block, time-block) with time minor-most
and sequential: the cross-block carry lives in VMEM scratch while the
within-block scan is a log-depth (Hillis-Steele) scan on a (block_t,
block_c) VMEM tile built from sublane rolls — VPU-friendly, no per-row
dynamic stores.

Oracle: ``repro.kernels.ref.linear_scan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, h0_ref, h_ref, hlast_ref, carry_ref, *, nt):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_ref[...] = h0_ref[...][:1, :]

    a = a_ref[...].astype(jnp.float32)                 # (bt, bc)
    x = x_ref[...].astype(jnp.float32)
    bt = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    # fold the cross-block carry into the first step: h_1 = a_1 h_0 + x_1
    h = x + jnp.where(row == 0, a * carry_ref[...], 0.0)

    # Hillis-Steele inclusive scan of the affine maps (a_t, h_t): after
    # the step with distance d, row i holds the composition of rows
    # (i-2d, i].  Sublane rolls keep every operation a whole-tile op.
    d = 1
    while d < bt:
        valid = row >= d
        h_prev = pltpu.roll(h, d, 0)                   # row i <- row i-d
        a_prev = pltpu.roll(a, d, 0)
        h = jnp.where(valid, a * h_prev + h, h)
        a = jnp.where(valid, a * a_prev, a)
        d *= 2

    h_ref[...] = h.astype(h_ref.dtype)
    carry_ref[...] = jnp.sum(jnp.where(row == bt - 1, h, 0.0), axis=0,
                             keepdims=True)

    @pl.when(it == nt - 1)
    def _finish():
        hlast_ref[...] = jnp.broadcast_to(carry_ref[...], hlast_ref.shape)


def linear_scan_pallas(a, x, h0=None, *, block_t=256, block_c=256,
                       interpret=False):
    """a, x: (B, S, C); h0: (B, C) or None.  Returns (h, h_last).
    block_t must be a multiple of 16 and block_c of 128 (``ops`` pads and
    picks them)."""
    b, s, c = a.shape
    if h0 is None:
        h0 = jnp.zeros((b, c), x.dtype)
    block_t = min(block_t, s)
    block_c = min(block_c, c)
    assert s % block_t == 0 and c % block_c == 0, "caller pads S/C"
    nt, nc = s // block_t, c // block_c

    seq_spec = pl.BlockSpec((None, block_t, block_c),
                            lambda b_, ic, it: (b_, it, ic))
    # per-row state travels as a whole (8, block_c) f32 tile, row 0 used
    row_spec = pl.BlockSpec((None, 8, block_c), lambda b_, ic, it: (b_, 0, ic))
    h0 = jnp.broadcast_to(h0.astype(jnp.float32)[:, None, :], (b, 8, c))
    h, h_last = pl.pallas_call(
        functools.partial(_kernel, nt=nt),
        grid=(b, nc, nt),
        in_specs=[seq_spec, seq_spec, row_spec],
        out_specs=[seq_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, c), x.dtype),
            jax.ShapeDtypeStruct((b, 8, c), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="linear_scan",
    )(a, x, h0)
    return h, h_last[:, 0].astype(x.dtype)
