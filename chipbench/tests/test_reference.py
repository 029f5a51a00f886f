"""The plain reference against the system's own model code, at a tiny
size on the CPU, at the highest matmul precision.  The system's
vocabulary padding is avoided (vocab 512), so the two must agree to
float32 rounding."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch
import system
import weights

HERE = Path(__file__).parent


@pytest.mark.parametrize("tie", [False, True])
def test_reference_logits_match_the_system(tie):
    from repro.kernels import ops

    c = dict(json.loads((HERE / "tiny_qwen2.json").read_text()),
             tie_word_embeddings=tie)
    model = system.build(c)
    w = weights.make(c, 2 ** 31 + 7)
    params = system.program_params(c, model, w)
    tok = np.random.default_rng(0).integers(3, c["vocab_size"], (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"), ops.use_backend("jnp"):
        got = np.asarray(jax.jit(lambda p, t: model.forward(p, t)[0])(params, tok))
    ref = arch.reference(c).Ref(c)
    want = np.asarray(ref.jit_logits(w, jnp.asarray(tok)))
    assert got.shape[-1] == c["vocab_size"]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # the leaf names map both ways
    back = system.leaves(c, params)
    assert sorted(back) == sorted(w)
    for k in w:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(w[k]))


def test_seed_key_takes_seeds_above_32_bits():
    a = weights.seed_key(2 ** 31 + 5)
    b = weights.seed_key(5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
