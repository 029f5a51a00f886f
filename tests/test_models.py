"""Per-architecture smoke tests (reduced configs per assignment: <=2
layers-per-pattern, d_model<=512, <=4 experts) + decode/forward
consistency across every family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_model_config, reduced
from repro.models.model import build_model, needs_prefix

KEY = jax.random.key(0)


def _inputs(cfg, b=2, s=12):
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    kw = {}
    if needs_prefix(cfg):
        kw["prefix_embeds"] = jax.random.normal(
            KEY, (b, cfg.n_prefix_tokens, cfg.prefix_dim)) * 0.1
    return toks, kw


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    """One forward + one gradient step on CPU: output shapes + no NaNs."""
    cfg = reduced(get_model_config(arch))
    assert cfg.n_layers <= 2 and cfg.d_model <= 512 and cfg.n_experts <= 4
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    toks, kw = _inputs(cfg)
    logits, aux = model.forward(params, toks, **kw)
    off = cfg.n_prefix_tokens if (needs_prefix(cfg) and not cfg.is_encdec) else 0
    assert logits.shape == (2, off + 12, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all())

    def loss_fn(p):
        lg, _ = model.forward(p, toks, **kw)
        return jnp.mean(lg.astype(jnp.float32) ** 2)

    grads = jax.grad(loss_fn)(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    """prefill + stepwise decode logits == full forward logits (the
    serving path and the scoring path must agree for RL correctness).
    MoE archs use a dropless capacity factor (capacity dropping is the
    one intentional train/serve divergence)."""
    cfg = reduced(get_model_config(arch))
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    toks, kw = _inputs(cfg)
    logits_full, _ = model.forward(params, toks, **kw)
    off = cfg.n_prefix_tokens if (needs_prefix(cfg) and not cfg.is_encdec) else 0
    pre = 5
    cache = model.init_cache(2, 64)
    lg, cache = model.prefill(params, toks[:, :pre], cache, **kw)
    errs = [float(jnp.abs(lg - logits_full[:, off + pre - 1]).max())]
    for t in range(pre, 12):
        lg, cache = model.decode_step(params, toks[:, t], cache)
        errs.append(float(jnp.abs(lg - logits_full[:, off + t]).max()))
    assert max(errs) < 5e-4, f"{arch}: decode diverges {max(errs)}"


@pytest.mark.parametrize("arch", ["olmo-1b", "xlstm-1.3b", "recurrentgemma-9b",
                                  "h2o-danube-1.8b", "olmoe-1b-7b"])
def test_packed_equals_separate(arch):
    """Two sequences packed into one row score identically to separate
    rows (block-diagonal masking / recurrence resets)."""
    cfg = reduced(get_model_config(arch))
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=16.0)
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    s1 = jax.random.randint(jax.random.key(1), (1, 6), 0, cfg.vocab_size)
    s2 = jax.random.randint(jax.random.key(2), (1, 6), 0, cfg.vocab_size)
    packed = jnp.concatenate([s1, s2], axis=1)
    seg = jnp.asarray([[0] * 6 + [1] * 6], jnp.int32)
    pos = jnp.asarray([list(range(6)) + list(range(6))], jnp.int32)
    h_packed, _ = model.hidden_states(params, packed, positions=pos,
                                      segment_ids=seg)
    h1, _ = model.hidden_states(params, s1)
    h2, _ = model.hidden_states(params, s2)
    np.testing.assert_allclose(np.asarray(h_packed[:, :6]), np.asarray(h1),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(h_packed[:, 6:]), np.asarray(h2),
                               atol=2e-3, rtol=2e-3)


def test_prefill_right_padding_inert():
    """Right-padded prompts: padded tail must not affect decode."""
    cfg = reduced(get_model_config("recurrentgemma-9b"))
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (1, 6), 3, cfg.vocab_size)
    # exact-length prefill
    c1 = model.init_cache(1, 32)
    lg1, c1 = model.prefill(params, toks, c1)
    # padded prefill with junk tail
    junk = jnp.full((1, 4), 7, jnp.int32)
    c2 = model.init_cache(1, 32)
    lg2, c2 = model.prefill(params, jnp.concatenate([toks, junk], 1), c2,
                            length=jnp.array([6], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               atol=2e-4, rtol=2e-4)
    nt = jnp.argmax(lg1, -1).astype(jnp.int32)
    d1, _ = model.decode_step(params, nt, c1)
    d2, _ = model.decode_step(params, nt, c2)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               atol=2e-4, rtol=2e-4)


def test_swa_cache_is_window_sized():
    cfg = reduced(get_model_config("h2o-danube-1.8b"))
    model = build_model(cfg, remat=False)
    cache = model.init_cache(1, 1000)
    k = cache["units"][0]["k"]
    assert k.shape[2] == cfg.sliding_window       # ring buffer = window


def test_long_decode_support_flags():
    flags = {a: get_model_config(a).supports_long_decode for a in ARCH_IDS}
    assert flags["xlstm-1.3b"] and flags["recurrentgemma-9b"] \
        and flags["h2o-danube-1.8b"]
    for a in ("olmo-1b", "phi3-medium-14b", "qwen3-moe-235b-a22b",
              "whisper-medium", "internvl2-2b", "minitron-8b", "olmoe-1b-7b"):
        assert not flags[a]


def test_param_counts_near_nameplate():
    """Analytic param count lands near each architecture's nameplate."""
    targets = {"minitron-8b": 8e9, "phi3-medium-14b": 14e9,
               "olmoe-1b-7b": 7e9, "recurrentgemma-9b": 9e9,
               "qwen3-moe-235b-a22b": 235e9, "olmo-1b": 1.2e9}
    for arch, t in targets.items():
        n = get_model_config(arch).param_count()
        assert 0.75 * t < n < 1.35 * t, f"{arch}: {n:.2e} vs {t:.2e}"


@pytest.mark.parametrize("fn", ["matmul", "unembed"])
def test_narrower_weight_is_met_in_its_dtype(fn):
    """A bf16 run weight against f32 activations (DESIGN.md §Rollout run
    weights): the activations are cast to bf16 and the weight is never
    converted back to f32, so XLA has nothing to re-round; equal dtypes
    keep the plain dot."""
    from repro.models import layers

    x = jax.random.normal(jax.random.key(0), (3, 16), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (16, 8), jnp.float32)
    call = {"matmul": layers.matmul,
            "unembed": lambda x, w: layers.unembed_apply(
                {}, {"w": w}, x, tie=False)}[fn]
    w16 = w.astype(jnp.bfloat16)
    jaxpr = jax.make_jaxpr(call)(x, w16).jaxpr
    dot = next(e for e in jaxpr.eqns if e.primitive.name == "dot_general")
    assert [v.aval.dtype for v in dot.invars] == [jnp.bfloat16] * 2
    assert not any(e.primitive.name == "convert_element_type"
                   and e.invars[0] is jaxpr.invars[1] for e in jaxpr.eqns)
    want = jnp.matmul(x.astype(jnp.bfloat16), w16,
                      preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(call(x, w16)), np.asarray(want))
    same = jax.make_jaxpr(call)(x, w).jaxpr
    assert [e.primitive.name for e in same.eqns][0] == "dot_general"
