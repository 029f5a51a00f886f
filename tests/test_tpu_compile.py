"""Compile-only checks for the TPU v5e: every Pallas kernel at real
widths, and one whole fused paged decode step of areal-qwen-1.5b at full
width and depth, compiled by the TPU compiler for a described (not
attached) chip.  Nothing runs; a pass means Mosaic accepted the kernel's
tiling and fast-memory use and that the kernel is in the compiled
program (``tpu_custom_call``).  Interpret-mode tests cannot see either.

See DESIGN.md §Running on the TPU.  The topology is described inside a
module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
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
