"""The architecture seam (``arch.py``): a configuration's ``model_type``
finds ``reference/<model_type>.py`` and ``programs/<model_type>.py``.

The pins hold what the Qwen2 cell read before its code moved behind the
seam: operation counts, the weights of a seed and the reference's
logits, each taken on the CPU (XLA's CPU backend, x86-64) before the
move.  A moved count, weight or product would show in the cell as a
moved yardstick, so each must stay equal, bit for bit.
"""
import hashlib
import importlib
import json
import re
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import arch
import flops
import weights
from test_faults import _run

HERE = Path(__file__).parent
ROOT = HERE.parent
CELL = json.loads((ROOT / "configs" / "areal-qwen-1.5b-4l.json").read_text())
TINY = json.loads((HERE / "tiny_qwen2.json").read_text())
LENS = [32 + 512, 40 + 377, 37 + 1, 29 + 512, 31 + 200]
SEED = 2 ** 31 + 7

# (sum, sum of squares) of each leaf of weights.make(TINY, SEED), in f64
WEIGHTS = {
    "attn_norm": ("0x1.0000000000000p+7", "0x1.0000000000000p+7"),
    "embed": ("0x1.35c1d9ee13f88p+1", "0x1.a82c279919090p+3"),
    "final_norm": ("0x1.0000000000000p+6", "0x1.0000000000000p+6"),
    "head": ("-0x1.9eb9621f8c400p+4", "0x1.00a958ab6e742p+9"),
    "mlp_norm": ("0x1.0000000000000p+7", "0x1.0000000000000p+7"),
    "w_down": ("0x1.a5fb8c496c000p+1", "0x1.f2ba353f189a8p+6"),
    "w_gate": ("-0x1.90a21c56f6000p+3", "0x1.feba414392fd2p+7"),
    "w_up": ("-0x1.06f767814aa00p+3", "0x1.052fe568a4bf4p+8"),
    "wk": ("0x1.7a4637caa2000p+2", "0x1.063fada86204cp+6"),
    "wo": ("0x1.2dfae1dc70000p+1", "0x1.ff33276b1ccf2p+6"),
    "wq": ("0x1.2ccd970e42c00p+4", "0x1.f708d25258d2bp+6"),
    "wv": ("0x1.25bd50b376600p+2", "0x1.fb8d323fc6edcp+5"),
}

# sha256 of the float32 logits (2, 40, 512) of _tokens() under TINY's
# weights of SEED: (tie_word_embeddings, dtype of the reference) -> digest
LOGITS = {
    (False, "float32"): "257588906cee288d2cd876bf4f3dfdd8c338011089b5084983a8ae31f2724039",
    (False, "bfloat16"): "750ec04f8862037cf9297f4c1329a9cb3c3d750a52052f2496eec8a4b1e0a736",
    (True, "float32"): "ec4d5be68d283933d0a2edcec64bc1f1b26c9990b34630badebffce8e07d15e1",
    (True, "bfloat16"): "383bcbe3a29d54c4084c5b5409e780c7cb1899426a6aa4f494615210bc591907",
}


def _tokens():
    return np.random.default_rng(0).integers(3, 512, (2, 40)).astype(np.int32)


# ---- pins ---------------------------------------------------------------------

def test_matmul_params_of_the_cell():
    # 4 layers of 46,792,704 and the untied head's 76,288 x 1,536
    assert flops.matmul_params(CELL) == 304_349_184 == 187_170_816 + 117_178_368


@pytest.mark.parametrize("c,train,decode", [
    (CELL, 3_264_209_068_032, 10_095_794_061_312),
    (TINY, 1_760_681_472, 6_049_660_928)], ids=["cell", "tiny"])
def test_train_and_decode_flops(c, train, decode):
    assert flops.train_flops(c, LENS) == train
    assert flops.decode_flops(c, 16384, 5_000_000) == decode


def test_weights_digest():
    w = weights.make(TINY, SEED)
    assert sorted(w) == sorted(WEIGHTS)
    for k, (s, s2) in WEIGHTS.items():
        x = np.asarray(w[k], np.float64)
        assert (float(np.sum(x)), float(np.sum(np.square(x)))) == (
            float.fromhex(s), float.fromhex(s2)), k


@pytest.mark.parametrize("tie,dtype", sorted(LOGITS))
def test_reference_logits_bit_for_bit(tie, dtype):
    c = dict(TINY, tie_word_embeddings=tie)
    ref = arch.reference(c).Ref(c, jnp.dtype(dtype))
    lg = np.asarray(ref.jit_logits(ref.cast(weights.make(c, SEED)),
                                   jnp.asarray(_tokens())))
    assert lg.shape == (2, 40, 512) and lg.dtype == np.float32
    assert hashlib.sha256(lg.tobytes()).hexdigest() == LOGITS[tie, dtype]


# ---- resolution -----------------------------------------------------------------

@pytest.mark.parametrize("call", [
    lambda c: arch.reference(c), lambda c: arch.program(c),
    lambda c: flops.matmul_params(c), lambda c: weights.make(c, 1)],
    ids=["reference", "program", "flops", "weights"])
def test_unknown_model_type_names_both_files(call):
    c = dict(TINY, model_type="no_such_arch")
    with pytest.raises(LookupError) as e:
        call(c)
    assert "chipbench/reference/no_such_arch.py" in str(e.value)
    assert "chipbench/programs/no_such_arch.py" in str(e.value)


QWEN2_NAMES = re.compile(
    r"\b(wq|wk|wv|wo|w_gate|w_up|w_down|attn_norm|mlp_norm|final_norm|qwen2)\b"
    r"|reference\.dense")


def test_only_the_architecture_files_know_qwen2():
    own = {ROOT / "reference" / "qwen2.py", ROOT / "programs" / "qwen2.py"}
    named = [f"{p.relative_to(ROOT)}:{i}"
             for p in sorted(ROOT.rglob("*.py"))
             if p not in own and "tests" not in p.relative_to(ROOT).parts
             for i, line in enumerate(p.read_text().splitlines(), 1)
             if QWEN2_NAMES.search(line)]
    assert not named, named
    assert not (ROOT / "reference" / "dense.py").exists()


@pytest.fixture
def toy(monkeypatch):
    """Qwen2's two modules stood in under another ``model_type``, with no
    file of the harness touched."""
    for pkg in ("reference", "programs"):
        monkeypatch.setitem(sys.modules, f"{pkg}.toy_arch",
                            importlib.import_module(f"{pkg}.qwen2"))
    return dict(TINY, model_type="toy_arch")


def test_toy_architecture_counts_and_weights(toy):
    assert flops.train_flops(toy, LENS) == flops.train_flops(TINY, LENS)
    a, b = weights.make(toy, SEED), weights.make(TINY, SEED)
    for k in b:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


@pytest.mark.parametrize("fault,correct", [
    ("", True), ("unchanged_state", False), ("half_batch", False),
    ("alter_token", False)])
def test_toy_architecture_runs_the_tiny_rl_cell(toy, tmp_path, fault, correct):
    (tmp_path / "toy.json").write_text(json.dumps(toy))
    limits = json.loads((ROOT / "limits" / "qwen1.5b-4l.rl-grpo.json").read_text())
    res = _run("rl", tmp_path / "toy.json", HERE / "tiny_rl.json", limits, 2.0,
               fault)
    assert res["correct"] is correct, res["checks"]
    assert res["ctx"]["window_steps"] >= 1
