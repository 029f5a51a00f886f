"""Activation-constraint tags: no-op without a mesh; GQA degradation to
replication on indivisible head counts; hypothesis sweep of random
shapes through the kernel ops dispatch."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.dist.constraints import constrain, constrain_qkv
from repro.kernels import ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 8))
    y = constrain(x, "dp", "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_qkv_noop_without_mesh():
    q = jnp.ones((2, 8, 4, 16))
    k = jnp.ones((2, 8, 2, 16))
    q2, k2, v2 = constrain_qkv(q, k, k)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))


def test_resolve_spec_degrades_indivisible_axes():
    """Entries whose axis sizes don't divide the dim (GQA kv heads, odd
    batches) or that name absent axes degrade to replication, never raise."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import constraints

    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    # 3 kv heads on a 4-way model axis -> replicated head dim
    spec = constraints.resolve_spec(mesh, (2, 8, 3, 64),
                                    ("dp", None, "model", None))
    assert spec == P("data", None, None, None)
    # "dp" drops when the batch doesn't divide the data axes
    spec = constraints.resolve_spec(mesh, (3, 8), ("dp", None))
    assert spec == P(None, None)
    # axis names absent from the mesh are dropped
    spec = constraints.resolve_spec(mesh, (4, 8), ("dp", "tensor"))
    assert spec == P("data", None)


@pytest.mark.slow
def test_constrain_qkv_gqa_indivisible_kv_heads():
    """GQA with n_kv_heads=1 on a 2-way model axis: k/v constraints must
    degrade to replication (q stays head-sharded) and leave the values
    bit-identical to the meshless path — not crash."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.constraints import constrain_qkv

        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(2, 8, 4, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 8, 1, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 8, 1, 16)), jnp.float32)
        f = lambda q, k, v: list(constrain_qkv(q, k, v))
        ref = jax.jit(f)(q, k, v)
        mesh = jax.make_mesh((1, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            out = jax.jit(f)(q, k, v)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(4, 48), st.sampled_from([1, 2, 4]),
       st.sampled_from([16, 32, 80]), st.booleans(),
       st.integers(0, 2**31 - 1))
def test_flash_attention_backends_agree_random_shapes(b, s, hkv, hd, win,
                                                      seed):
    """Hypothesis sweep: pallas-interpret == jnp oracle on random shapes."""
    rng = np.random.default_rng(seed)
    h = hkv * int(rng.integers(1, 3))
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    window = int(rng.integers(1, s + 1)) if win else 0
    o1 = ops.flash_attention(q, k, v, window=window, backend="jnp")
    o2 = ops.flash_attention(q, k, v, window=window,
                             backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                               atol=3e-5, rtol=3e-5)


def test_backend_switch_roundtrip():
    """The backend is the platform's unless a scope says otherwise, and
    the scope restores it on exit; interpret mode is per call only."""
    assert ops.platform_backend() == "jnp"            # this suite runs on CPU
    assert ops.current_backend() == "jnp"
    q = jnp.ones((1, 8, 2, 16))

    def prims(backend):
        with ops.use_backend(backend):
            jaxpr = jax.make_jaxpr(
                lambda x: ops.flash_attention(x, x, x))(q)
        return {e.primitive.name for e in jaxpr.jaxpr.eqns}

    assert "pallas_call" in prims("pallas")
    assert "pallas_call" not in prims("jnp")
    assert ops.current_backend() == "jnp"
    with pytest.raises(ValueError):
        with ops.use_backend("pallas_interpret"):
            pass
    out = ops.flash_attention(q, q, q, backend="pallas_interpret")
    assert out.shape == q.shape
