"""Compile-only checks for the TPU v5e: every Pallas kernel at real
widths, one whole fused paged decode step of areal-qwen-1.5b at full
width and depth, and the rollout's decode and chunk steps on its run
weights, compiled by the TPU compiler for a described (not
attached) chip.  Nothing runs; a pass means Mosaic accepted the kernel's
tiling and fast-memory use and that the kernel is in the compiled
program (``tpu_custom_call``).  Interpret-mode tests cannot see either.

See DESIGN.md §Running on the TPU.  The topology is described inside a
module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_model_config
from repro.kernels import ops
from repro.models import model as model_mod
from repro.models.model import build_model

QWEN = get_model_config("areal-qwen-1.5b")
LRU_WIDTH = get_model_config("recurrentgemma-9b").lru_width
SLOTS, MAX_LEN, BLOCK, CHUNK = 16, 1024, 16, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    with ops.use_backend("pallas"):
        return jax.jit(fn).lower(*args).compile()


def _kernel_case(name, dt, S):
    h, hkv, hd, d = QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim, QWEN.d_model
    e = MAX_LEN // BLOCK
    pool = (SLOTS * e, BLOCK, hkv, hd)
    i32 = jnp.int32
    if name == "paged_decode_attention":
        return ops.paged_decode_attention, (
            S((SLOTS, h, hd), dt), S(pool, dt), S(pool, dt),
            S((SLOTS, e), i32), S((SLOTS,), i32))
    if name == "fused_decode_tail":
        return ops.fused_decode_tail, (
            S((SLOTS, h, hd), dt), S(pool, dt), S(pool, dt),
            S((h * hd, d), dt), S((SLOTS, e), i32), S((SLOTS,), i32))
    if name == "paged_prefill_attention":
        return ops.paged_prefill_attention, (
            S((2, CHUNK, h, hd), dt), S(pool, dt), S(pool, dt),
            S((2, e), i32), S((2, CHUNK), i32))
    if name == "flash_attention":
        return ops.flash_attention, (
            S((2, MAX_LEN, h, hd), dt), S((2, MAX_LEN, hkv, hd), dt),
            S((2, MAX_LEN, hkv, hd), dt), S((2, MAX_LEN), i32))
    if name == "decode_attention":
        return ops.decode_attention, (
            S((SLOTS, h, hd), dt), S((SLOTS, MAX_LEN, hkv, hd), dt),
            S((SLOTS, MAX_LEN, hkv, hd), dt), S((SLOTS, MAX_LEN), i32),
            S((SLOTS,), i32))
    assert name == "linear_scan"
    return ops.linear_scan, (
        S((2, MAX_LEN, LRU_WIDTH), dt), S((2, MAX_LEN, LRU_WIDTH), dt),
        S((2, LRU_WIDTH), dt))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("name", ["paged_decode_attention", "fused_decode_tail",
                                  "paged_prefill_attention", "flash_attention",
                                  "decode_attention", "linear_scan"])
def test_kernel_compiles_for_v5e(name, dtype, one_chip):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn, args = _kernel_case(name, dtype, S)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def full_width(one_chip):
    """areal-qwen-1.5b as published (28 layers, vocab 151,936) with f32
    parameters (the engine default) and a paged KV pool of 16 slots x
    1024 tokens, as shapes on one described chip."""
    model = build_model(QWEN, remat=False)
    put = lambda t: jax.tree.map(                           # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    params = put(model_mod.param_specs(model, QWEN, jnp.float32))
    cache, tables = model_mod.paged_cache_specs(
        model, QWEN, SLOTS, MAX_LEN, BLOCK, SLOTS * MAX_LEN // BLOCK,
        jnp.float32)
    return model, params, put(cache), tables.shape[1]


def test_fused_paged_decode_step_compiles_at_full_width(one_chip, full_width):
    """One decode step of the fused paged engine path at full width and
    depth — within one 16 GB chip."""
    from repro.launch import steps

    model, params, cache, e = full_width
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,    # noqa: E731
                                          sharding=one_chip)
    compiled = _compile(steps.make_fused_serve_step(model), params,
                        i32(SLOTS), cache, i32(SLOTS, e))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16e9, used


def test_paged_prefill_chunk_step_compiles_at_full_width(one_chip, full_width):
    """One chunked-prefill ingest step of the paged engine path (two rows
    of a 64-token span) at full width and depth."""
    from repro.launch import steps

    model, params, cache, e = full_width
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,    # noqa: E731
                                          sharding=one_chip)
    rows = 2
    compiled = _compile(steps.make_paged_prefill_chunk_step(model), params,
                        i32(rows, CHUNK), cache, i32(rows, e),
                        i32(rows, CHUNK), i32(rows), i32(rows), i32(rows))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def cell_width(one_chip):
    """The benchmark cell's model (areal-qwen-1.5b at 4 layers, a
    76,288-row vocabulary and an untied head) with its f32 parameters
    and with the run weights the engine casts from them, and the cell's
    paged pool: 32 slots x 544 tokens in f32, as shapes on one described
    chip."""
    cfg = dataclasses.replace(QWEN, n_layers=4, vocab_size=76_288,
                              tie_embeddings=False)
    model = build_model(cfg, remat=False)
    put = lambda t: jax.tree.map(                           # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    params = model_mod.param_specs(model, cfg, jnp.float32)
    run = jax.eval_shape(
        lambda p: model_mod.run_weights(p, jnp.bfloat16), params)
    slots, max_len = 32, 544
    cache, tables = model_mod.paged_cache_specs(
        model, cfg, slots, max_len, BLOCK, slots * (max_len // BLOCK),
        jnp.float32)
    return model, {"f32": put(params), "run": put(run)}, put(cache), tables.shape


def _weight_converts(hlo: str):
    """Element counts of the bf16 converts of an optimised HLO module
    whose operand is weight-sized (at least 2**20 elements)."""
    sizes = (math.prod(int(d) for d in m.group(1).split(","))
             for m in re.finditer(r"= bf16\[([\d,]+)\]\S* convert\(", hlo))
    return sorted(n for n in sizes if n >= 1 << 20)


@pytest.mark.parametrize("tree", ["f32", "run"])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_run_weights_take_the_converts_out_of_rollout_steps(
        step, tree, one_chip, cell_width):
    """On f32 weights XLA rounds every stacked layer weight to bf16 in
    each decode and chunk program, seven converts of 187,170,816
    elements in all; on the engine's run weights (DESIGN.md §Rollout
    run weights) no weight-sized convert is left."""
    from repro.launch import steps

    model, trees, cache, (slots, e) = cell_width
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,    # noqa: E731
                                          sharding=one_chip)
    if step == "decode":
        compiled = _compile(steps.make_paged_serve_step(model), trees[tree],
                            i32(slots), cache, i32(slots, e))
    else:
        compiled = _compile(steps.make_paged_prefill_chunk_step(model),
                            trees[tree], i32(1, 16), cache, i32(1, e),
                            i32(1, 16), i32(1), i32(1), i32(1))
    sizes = _weight_converts(compiled.as_text())
    if tree == "f32":
        assert len(sizes) == 7 and sum(sizes) == 187_170_816, sizes
    else:
        assert sizes == []
