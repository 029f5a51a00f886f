"""Interruptible rollout engine: continuous batching, EOS handling, and
the Proposition-1 property — an interruption with UNCHANGED weights is
bit-identical to uninterrupted generation (the KV/state recompute is
exact and the RNG stream untouched)."""
import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.config import EngineConfig
from repro.core.rollout import RolloutEngine
from repro.data import tokenizer
from repro.models.model import build_model


def _tiny(family="dense", **kw):
    base = dict(name="t", family=family, n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, d_ff=64, vocab_size=tokenizer.VOCAB_SIZE)
    base.update(kw)
    return ModelConfig(**base)


def _engine(cfg, seed=0, n_slots=4, **kw):
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(7))
    return model, params, RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=n_slots, prompt_len=8, max_gen_len=6, seed=seed, **kw))


def _reqs(n, start=0):
    return [{"rid": start + i, "prompt_id": start + i,
             "prompt": [1, 4 + i, 5, 6], "answer": None} for i in range(n)]


def _run_to_completion(engine, reqs, interrupt_at=(), new_params=None):
    """Drive ``reqs`` to completion, interrupting before each step in
    ``interrupt_at``: with the same weights, or with ``new_params`` under
    the next version."""
    done = {}
    pending = list(reqs)
    step = 0
    while len(done) < len(reqs):
        n = engine.admit(pending)
        pending = pending[n:]
        if step in interrupt_at:
            if new_params is None:                 # same weights
                engine.update_weights(engine.params, engine.version)
            else:
                engine.update_weights(new_params, engine.version + 1)
        for f in engine.step():
            done[f.rid] = f
        step += 1
        assert step < 500
    return done


@pytest.mark.parametrize("cache", ["ring", "paged"])
@pytest.mark.parametrize("family,extra", [
    ("dense", {}),
    ("dense", {"sliding_window": 4}),
    ("hybrid", {"block_pattern": ("rec", "local"), "d_ff": 64,
                "local_window": 4}),
    ("ssm", {"block_pattern": ("mlstm", "slstm"), "d_ff": 0,
             "n_kv_heads": 4}),
])
def test_interruption_with_same_weights_is_identity(family, extra, cache):
    cfg = _tiny(family, **extra)
    _, _, e1 = _engine(cfg, seed=3, cache=cache, block_size=4)
    _, _, e2 = _engine(cfg, seed=3, cache=cache, block_size=4)
    d1 = _run_to_completion(e1, _reqs(4))
    d2 = _run_to_completion(e2, _reqs(4), interrupt_at=(1, 3))
    assert e2.interruptions == 2
    for rid in d1:
        assert d1[rid].response == d2[rid].response, family
        np.testing.assert_allclose(d1[rid].logprobs, d2[rid].logprobs,
                                   atol=1e-4)


def test_version_tags_span_interruption():
    cfg = _tiny()
    model, params, e = _engine(cfg, n_slots=2)
    e.admit(_reqs(2))
    e.step()
    # new weights -> in-flight trajectories get mixed version tags
    new_params = jax.tree.map(lambda x: x * 1.01, params)
    applied = e.update_weights(new_params, version=1)
    assert applied and e.interruptions == 1
    done = {}
    steps = 0
    while len(done) < 2 and steps < 100:
        for f in e.step():
            done[f.rid] = f
        steps += 1
    for f in done.values():
        assert set(f.versions) <= {0, 1}
        assert f.versions == sorted(f.versions)
        assert len(f.versions) == len(f.response)
        assert f.behavior_version == 0


def test_non_interruptible_defers_until_drain():
    cfg = _tiny()
    model, params, e = _engine(cfg, n_slots=2)
    e.admit(_reqs(2))
    e.step()
    applied = e.update_weights(params, version=1, interruptible=False)
    assert not applied and e.has_pending_weights
    assert e.version == 0
    while e.n_active:
        e.step()
    assert e.maybe_apply_pending()
    assert e.version == 1 and not e.has_pending_weights


def test_slot_reuse_and_eos():
    cfg = _tiny()
    _, _, e = _engine(cfg, n_slots=2)
    done = _run_to_completion(e, _reqs(6))
    assert len(done) == 6
    for f in done.values():
        assert 1 <= len(f.response) <= 6
        assert len(f.logprobs) == len(f.response)
        if not f.truncated:
            assert f.response[-1] == tokenizer.EOS
        # behavior logprobs are valid log-probabilities
        assert all(lp <= 1e-6 for lp in f.logprobs)


def test_inflight_tokens_accounting():
    cfg = _tiny()
    _, _, e = _engine(cfg, n_slots=4)
    assert e.inflight_tokens() == 0
    e.admit(_reqs(3))
    assert e.inflight_tokens() == 3 * 4      # three 4-token prompts
    e.step()
    assert e.inflight_tokens() == 3 * 5


# ---------------------------------------------------------------------------
# Paged cache engine (DESIGN.md §Paged KV-cache pool)
# ---------------------------------------------------------------------------

def _group_reqs(n_groups, group, prompt_len=6):
    """GRPO-style groups: ``group`` samples of each prompt."""
    out = []
    for gi in range(n_groups):
        prompt = [1, 40 + gi] + [5 + (gi + j) % 7 for j in range(prompt_len - 2)]
        for k in range(group):
            out.append({"rid": gi * group + k, "prompt_id": gi,
                        "prompt": prompt, "answer": None})
    return out


@pytest.mark.parametrize("family,extra", [
    ("dense", {}),
    ("dense", {"sliding_window": 4}),
    ("hybrid", {"block_pattern": ("rec", "local"), "d_ff": 64,
                "local_window": 4}),
])
def test_paged_engine_matches_ring_engine(family, extra):
    """Identical seeds -> the paged engine reproduces the ring engine's
    trajectories exactly, including prefix-shared GRPO groups."""
    cfg = _tiny(family, **extra)
    _, _, e_ring = _engine(cfg, seed=5)
    _, _, e_paged = _engine(cfg, seed=5, cache="paged", block_size=4)
    reqs = _group_reqs(3, 2)
    d1 = _run_to_completion(e_ring, reqs)
    d2 = _run_to_completion(e_paged, reqs)
    for rid in d1:
        assert d1[rid].response == d2[rid].response, family
        np.testing.assert_allclose(d1[rid].logprobs, d2[rid].logprobs,
                                   atol=1e-4)
    # groups share full prompt blocks: the 2nd sample of each group reuses
    assert e_paged.prefix_reused_blocks > 0
    # every block returned to the free list once all slots drained
    assert e_paged.allocator.n_live == 0


def test_paged_prefix_sharing_across_update_weights():
    """Prefix-shared groups survive a real (changed-weights) interrupt:
    the re-prefill rewrites each shared physical block once — not once
    per slot — and sharing persists for post-interrupt admissions."""
    cfg = _tiny()
    model, params, e = _engine(cfg, n_slots=4, cache="paged", block_size=4)
    e.admit(_group_reqs(1, 4, prompt_len=8))   # one group of 4, 2 full blocks
    assert e.prefix_reused_blocks == 3 * 2     # 3 followers x 2 shared blocks
    e.step()
    new_params = jax.tree.map(lambda x: x * 1.01, params)
    assert e.update_weights(new_params, version=1)
    # invalidated writes: 2 shared prompt blocks (8 tokens, written ONCE)
    # + one partial per-slot block holding the first fed response token
    assert e.reprefill_tokens == 8 + 4 * 1
    done = {}
    steps = 0
    while len(done) < 4 and steps < 100:
        for f in e.step():
            done[f.rid] = f
        steps += 1
    assert len(done) == 4
    for f in done.values():
        assert set(f.versions) <= {0, 1}
        assert len(f.versions) == len(f.response)
    assert e.allocator.n_live == 0
    # a fresh admission of the same prompt under v1 shares again
    before = e.prefix_reused_blocks
    e.admit(_group_reqs(1, 2, prompt_len=8))
    assert e.prefix_reused_blocks == before + 2


def test_paged_new_params_without_version_bump_still_rewrites():
    """Version tags can't detect staleness when the caller swaps params
    without bumping the version: the paged engine must fall back to a
    full rewrite (like the ring engine) instead of silently decoding
    new-weight queries against old-weight KV."""
    cfg = _tiny()
    model, params, e_ring = _engine(cfg, seed=4, cache="ring")
    _, _, e_paged = _engine(cfg, seed=4, cache="paged", block_size=4)
    new_params = jax.tree.map(lambda x: x * 1.02, params)
    reqs = _reqs(3)

    def run(e):
        done, pending, step = {}, list(reqs), 0
        while len(done) < len(reqs):
            k = e.admit(pending)
            pending = pending[k:]
            if step == 1:
                e.update_weights(new_params, version=e.version)  # no bump
            for f in e.step():
                done[f.rid] = f
            step += 1
            assert step < 300
        return done

    d1, d2 = run(e_ring), run(e_paged)
    assert e_paged.reprefill_tokens > 0        # the forced rewrite happened
    for rid in d1:
        assert d1[rid].response == d2[rid].response
        np.testing.assert_allclose(d1[rid].logprobs, d2[rid].logprobs,
                                   atol=1e-4)


def test_paged_empty_prompt_matches_ring_after_pool_reuse():
    """An empty prompt still feeds one pad token whose KV must be
    written: a freshly allocated pool block can hold a *released*
    request's contents, so a dropped write would make the output depend
    on allocation history (regression test)."""
    cfg = _tiny()
    _, _, e_ring = _engine(cfg, seed=9, n_slots=2)
    _, _, e_paged = _engine(cfg, seed=9, n_slots=2, cache="paged",
                            block_size=4)
    # first a normal request dirties pool blocks, then an empty prompt
    reqs = [{"rid": 0, "prompt_id": 0, "prompt": [1, 4, 5, 6], "answer": None}]
    d1 = dict(_run_to_completion(e_ring, reqs))
    d2 = dict(_run_to_completion(e_paged, reqs))
    empty = [{"rid": 1, "prompt_id": 1, "prompt": [], "answer": None}]
    d1.update(_run_to_completion(e_ring, empty))
    d2.update(_run_to_completion(e_paged, empty))
    for rid in d1:
        assert d1[rid].response == d2[rid].response
    # and across a same-weights interrupt: BOTH engines' re-prefills
    # must re-feed the pad token (the seed ring engine dropped it,
    # shifting every position by one)
    for kw in ({}, {"cache": "paged", "block_size": 4}):
        _, _, e3 = _engine(cfg, seed=9, n_slots=2, **kw)
        d3 = dict(_run_to_completion(e3, reqs))
        d3.update(_run_to_completion(e3, empty, interrupt_at=(1,)))
        for rid in d1:
            assert d1[rid].response == d3[rid].response, kw


def test_paged_pool_exhaustion_defers_admission():
    """A pool too small for every slot admits what fits; finished slots
    return blocks and the rest are admitted later."""
    cfg = _tiny()
    # each request needs ceil((4 + 6 - 1) / 4) = 3 blocks; pool of 7
    # admits two distinct prompts, not three
    model, params, e = _engine(cfg, n_slots=4, cache="paged",
                               block_size=4, n_blocks=7)
    reqs = _reqs(3)
    n = e.admit(reqs)
    assert n == 2 and e.allocator.n_free == 1
    done = {}
    pending = reqs[n:]
    steps = 0
    while len(done) < 3 and steps < 200:
        k = e.admit(pending)
        pending = pending[k:]
        for f in e.step():
            done[f.rid] = f
        steps += 1
    assert len(done) == 3
    assert e.allocator.n_live == 0


def test_paged_blocks_scale_with_history_not_max_len():
    """The memory story: live blocks track what slots actually hold
    (shared prompts counted once), not n_slots * max_len."""
    cfg = _tiny()
    _, _, e = _engine(cfg, n_slots=4, cache="paged", block_size=4)
    e.admit(_group_reqs(1, 4, prompt_len=8))
    # ring equivalent: 4 slots x ceil(max_len/bs) = 4 * ceil(14/4) = 16
    # paged: 2 shared prompt blocks + 4 slots x ceil((8+6-1)/4 - 2) tail
    assert e.allocator.n_live == 2 + 4 * 2
    assert e.blocks_in_use() < 4 * (-(-e.max_len // 4))


# ---------------------------------------------------------------------------
# Chunked prefill (DESIGN.md §Chunked prefill)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache", ["ring", "paged"])
@pytest.mark.parametrize("family,extra", [
    ("dense", {}),
    ("dense", {"sliding_window": 4}),
    ("hybrid", {"block_pattern": ("rec", "local"), "d_ff": 64,
                "local_window": 4}),
    ("ssm", {"block_pattern": ("mlstm", "slstm"), "d_ff": 0,
             "n_kv_heads": 4}),
])
def test_chunked_engine_matches_monolithic(family, extra, cache):
    """Chunk size 3 (NOT a divisor of prompt or block size) vs the
    monolithic engine under per-request RNG streams: identical
    trajectories per architecture family — including with same-weights
    interrupts landing MID-CHUNK (step 0: admission ingest is in flight)."""
    if cache == "paged" and family == "ssm":
        pytest.skip("paged cache needs an attention layer")
    cfg = _tiny(family, **extra)
    kw = {"cache": cache, "block_size": 4} if cache == "paged" else {}
    _, _, e_mono = _engine(cfg, seed=3, rng="request", **kw)
    _, _, e_chunk = _engine(cfg, seed=3, prefill_chunk=3, **kw)
    d1 = _run_to_completion(e_mono, _reqs(4))
    d2 = _run_to_completion(e_chunk, _reqs(4))
    for rid in d1:
        assert d1[rid].response == d2[rid].response, family
        np.testing.assert_allclose(d1[rid].logprobs, d2[rid].logprobs,
                                   atol=1e-4)
    # Prop. 1 under chunking: interrupts at steps 0 and 2 land while the
    # ingest queue is non-empty, forcing mid-chunk re-ingestion
    _, _, e_int = _engine(cfg, seed=3, prefill_chunk=3, **kw)
    d3 = _run_to_completion(e_int, _reqs(4), interrupt_at=(0, 2))
    assert e_int.interruptions == 2
    for rid in d1:
        assert d1[rid].response == d3[rid].response, (family, "interrupt")
        np.testing.assert_allclose(d1[rid].logprobs, d3[rid].logprobs,
                                   atol=1e-4)


def test_chunked_changed_weights_interrupt_ring_matches_paged():
    """A CHANGED-weights interrupt landing mid-ingest: ring-chunked and
    paged-chunked engines see the identical schedule (chunk == block
    size, so span plans agree) and must produce identical trajectories,
    with version tags spanning the interrupt."""
    cfg = _tiny()
    model, params, e_ring = _engine(cfg, seed=5, prefill_chunk=4)
    _, _, e_paged = _engine(cfg, seed=5, prefill_chunk=4, cache="paged",
                            block_size=4)
    new_params = jax.tree.map(lambda x: x * 1.01, params)
    reqs = _reqs(4)

    def run(e):
        done, pending, step = {}, list(reqs), 0
        while len(done) < len(reqs):
            k = e.admit(pending)
            pending = pending[k:]
            if step == 1:                  # admission ingest still queued
                e.update_weights(new_params, version=1)
            for f in e.step():
                done[f.rid] = f
            step += 1
            assert step < 300
        return done

    d1, d2 = run(e_ring), run(e_paged)
    for rid in d1:
        assert d1[rid].response == d2[rid].response
        np.testing.assert_allclose(d1[rid].logprobs, d2[rid].logprobs,
                                   atol=1e-4)
        assert set(d1[rid].versions) <= {0, 1}
        assert d1[rid].versions == sorted(d1[rid].versions)


def test_chunked_decode_runs_between_ingest_spans():
    """The point of chunking: once slot 0's prompt is in, it decodes
    while slot 1 is still ingesting (stat: decode_steps_during_prefill),
    and admission itself never runs a prefill."""
    cfg = _tiny()
    _, _, e = _engine(cfg, seed=1, n_slots=2, prefill_chunk=2)
    assert e.admit(_reqs(2)) == 2
    assert e.prefill_tokens == 0           # admission did not prefill
    assert e.n_active == 2
    sampled_during_backlog = False
    steps = 0
    while e._ingest_queue and steps < 50:
        e.step()
        if e.tokens_generated > 0 and e._ingest_queue:
            sampled_during_backlog = True
        steps += 1
    assert sampled_during_backlog
    assert e.stats()["decode_steps_during_prefill"] > 0
    # and the backlog metric drains to zero
    assert e.ingest_backlog_tokens() == 0


def test_chunked_engine_progresses_under_per_step_weight_refresh():
    """Forward-progress guarantee: weight publications arriving faster
    than the re-ingest backlog drains (one per engine step — the
    --refresh-every 1 regime) must not livelock the chunked engine.
    When no slot can decode there is nothing to overlap with, so step()
    keeps ingesting until the head slot's history is back (regression
    test for the one-span-per-step livelock)."""
    cfg = _tiny()
    _, _, e = _engine(cfg, seed=2, n_slots=2, prefill_chunk=2)
    pending = _reqs(4)
    done, steps = {}, 0
    while len(done) < 4:
        n = e.admit(pending)
        pending = pending[n:]
        e.update_weights(e.params, e.version + 1)   # every single step
        for f in e.step():
            done[f.rid] = f
        steps += 1
        assert steps < 300, "chunked engine livelocked under per-step refresh"
    assert all(len(f.response) >= 1 for f in done.values())
    # accounting: redone spans of interrupted admissions count as
    # reprefill work, never as additional prompt prefill (fresh prefill
    # is bounded by the total prompt tokens admitted)
    assert e.prefill_tokens <= sum(max(len(r["prompt"]), 1) for r in _reqs(4))
    assert e.reprefill_tokens > 0


def _inside(inner, outer):
    return (inner["line"] == outer["line"]
            and outer["start"] <= inner["start"] <= inner["end"] <= outer["end"])


def test_engine_phase_spans_under_profiler(profiled):
    """DESIGN.md §Telemetry: under a profiler session a paged, chunked
    engine writes its phases into the trace.  Every decode step nests
    rollout.decode.prepare, .wait and .finish inside rollout.step, in
    that order; the steps after an interrupt carry its re-prefill as
    rollout.ingest; and the sampled tokens and log-probs are bitwise
    those of the same run with no profiler."""
    cfg = _tiny()
    kw = dict(seed=3, cache="paged", block_size=4, prefill_chunk=3)
    _, params, e_off = _engine(cfg, **kw)
    new = jax.tree.map(lambda x: x * 1.01, params)
    d_off = _run_to_completion(e_off, _reqs(4), (2,), new)
    _, _, e_on = _engine(cfg, **kw)
    with profiled.session():
        d_on = _run_to_completion(e_on, _reqs(4), (2,), new)
    assert e_on.interruptions == 1 and e_on.reprefill_tokens > 0
    assert {r: (f.response, f.logprobs) for r, f in d_on.items()} == {
        r: (f.response, f.logprobs) for r, f in d_off.items()}

    spans = profiled.spans("rollout.")
    steps = [x for x in spans if x["name"] == "rollout.step"]
    flip, = [x for x in spans if x["name"] == "rollout.update_weights"]
    assert flip["stats"]["version"] == 1
    assert any(x["name"] == "rollout.admit" for x in spans)
    n_decode = 0
    for st in steps:
        inner = [x for x in spans if x is not st and _inside(x, st)]
        names = [x["name"] for x in inner if x["name"] != "rollout.ingest"]
        if "rollout.decode.wait" in names:
            n_decode += 1
            assert names == ["rollout.decode.prepare", "rollout.decode.wait",
                             "rollout.decode.finish"]
        else:
            assert names in ([], ["rollout.decode.prepare"])
    assert n_decode == e_on.decode_dispatches
    ingests = [x for x in spans if x["name"] == "rollout.ingest"]
    assert all(any(_inside(x, st) for st in steps) for x in ingests)
    # every slot is re-queued: the first step after the interrupt
    # re-ingests the head slot's history in several chunks
    after = next(st for st in steps if st["start"] >= flip["end"])
    assert sum(_inside(x, after) for x in ingests) >= 2


def test_chunked_rng_scheme_is_enforced():
    cfg = _tiny()
    with pytest.raises(ValueError, match="rng='request'"):
        _engine(cfg, prefill_chunk=2, rng="step")


def test_chunked_paged_pool_exhaustion_defers_and_counts():
    """Chunked admission reserves blocks exactly like monolithic
    admission: a pool too small defers the remainder AND surfaces the
    deferral in stats() so the scheduler can react without re-probing
    free_slots() (which cannot see block headroom)."""
    cfg = _tiny()
    _, _, e = _engine(cfg, n_slots=4, cache="paged", block_size=4,
                      n_blocks=7, prefill_chunk=4)
    reqs = _reqs(3)
    n = e.admit(reqs)
    assert n == 2 and e.deferred_last == 1 and e.deferred == 1
    done, pending, steps = {}, reqs[n:], 0
    while len(done) < 3 and steps < 300:
        k = e.admit(pending)
        pending = pending[k:]
        for f in e.step():
            done[f.rid] = f
        steps += 1
    assert len(done) == 3
    assert e.allocator.n_live == 0


def test_scheduler_starves_stream_pulls_on_engine_deferral():
    """AsyncScheduler.admitted(deferred=k > 0) stops fresh stream pulls:
    only the deferred backlog is re-offered until the engine reports it
    can take work again (the chunked-admission satellite fix)."""
    from repro.configs.base import RLConfig
    from repro.core import AsyncScheduler
    from repro.core.simulator import SimPromptStream

    rl = RLConfig(batch_size=8, max_staleness=4)
    sched = AsyncScheduler(prompt_stream=SimPromptStream(64), rl=rl)
    reqs = sched.plan_admission(4)
    assert len(reqs) == 4
    # engine took 1, deferred 2 on pool pressure (1 had no free slot)
    sched.admitted(reqs, 1, deferred=2)
    again = sched.plan_admission(4)
    # only the requeued backlog — no fresh stream pulls while starved
    assert [r["rid"] for r in again] == [1, 2, 3]
    sched.admitted(again, 3, deferred=0)   # engine recovered
    fresh = sched.plan_admission(2)
    assert [r["rid"] for r in fresh] == [4, 5]


def _threaded_chunked_runtime():
    from repro.configs.base import RLConfig
    from repro.core import AsyncScheduler, PPOTrainer, ThreadedRuntime
    from repro.data.dataset import PromptStream
    from repro.models.model import build_model

    cfg = _tiny()
    rl = RLConfig(batch_size=4, answers_per_prompt=2, max_staleness=2,
                  interruptible=True, ppo_minibatches=1,
                  microbatch_token_budget=64, lr=1e-3,
                  max_prompt_len=8, max_gen_len=6)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(2))
    engine = RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=4, prompt_len=8, max_gen_len=6, seed=2, prefill_chunk=2))
    trainer = PPOTrainer(model, rl, params)
    sched = AsyncScheduler(
        prompt_stream=PromptStream(seed=2, answers_per_prompt=2,
                                   max_operand=9), rl=rl)
    return ThreadedRuntime(engine=engine, trainer=trainer, scheduler=sched)


def test_threaded_runtime_with_chunked_engine():
    """The threaded runtime over a REAL chunked engine: the run
    completes, and decode steps demonstrably occur while the ingest
    queue is non-empty (generation never waits for a whole prefill)."""
    rt = _threaded_chunked_runtime()
    engine = rt.engine
    hist = rt.run(2, timeout=300)
    assert [h.version for h in hist] == [1, 2]
    assert engine.tokens_generated > 0
    assert engine.stats()["decode_steps_during_prefill"] > 0


def test_threaded_runtime_spans_under_profiler(profiled):
    """Under a profiler session the rollout thread's ticks hold its
    admissions, interrupts and engine steps, and the trainer's steps lie
    on the trainer thread's line (DESIGN.md §Telemetry).  The final
    pickup after the run is the calling thread's, outside any tick."""
    rt = _threaded_chunked_runtime()
    with profiled.session():
        rt.run(2, timeout=300)
    spans = profiled.spans("rollout.") + profiled.spans("trainer.")
    by = {}
    for x in spans:
        by.setdefault(x["name"], []).append(x)
    assert len(by["trainer.train_step"]) == 2
    assert by["rollout.update_weights"]
    ticks = by["rollout.tick"]
    lines = {x["line"] for x in ticks}
    for name in ("rollout.admit", "rollout.update_weights", "rollout.step"):
        mine = [x for x in by[name] if x["line"] in lines]
        assert mine and all(any(_inside(x, t) for t in ticks)
                            for x in mine), name
    assert lines.isdisjoint(x["line"] for x in by["trainer.train_step"])


def test_single_driver_contract_enforced():
    """The engine is single-driver (DESIGN.md §Async runtime): once a
    thread drives it, a second thread fails loudly instead of silently
    corrupting slot state; release_driver() allows a deliberate handoff."""
    import threading

    cfg = _tiny()
    _, _, e = _engine(cfg)
    err = []

    def drive():
        try:
            e.admit(_reqs(2))
            e.step()
        except BaseException as exc:        # pragma: no cover - fail path
            err.append(exc)

    t = threading.Thread(target=drive)
    t.start()
    t.join()
    assert not err
    with pytest.raises(RuntimeError, match="single-driver"):
        e.step()
    with pytest.raises(RuntimeError, match="single-driver"):
        e.update_weights(e.params, e.version + 1)
    e.release_driver()                      # deliberate handoff
    e.step()                                # main thread is the driver now
    assert e.tokens_generated >= 4


def test_controller_requeues_paged_pool_exhaustion():
    """A paged engine that admits fewer requests than offered (pool
    exhaustion) must not crash the virtual executor: the scheduler
    requeues the remainder and the run completes (DESIGN.md §Async
    runtime)."""
    from repro.configs.base import RLConfig
    from repro.core import AsyncRLController, TimingModel
    from repro.core.simulator import SimTrainer

    class _Stream:
        def __init__(self):
            self.n = 0

        def next_request(self):
            class P:
                prompt_tokens = [1, 2, 3, 4, 5, 6, 7, 8]
                answer = None
            self.n += 1
            return P(), self.n

    cfg = _tiny()
    # pool sized so only ~2 of 4 slots fit at once: admission is
    # persistently partial
    _, params, e = _engine(cfg, n_slots=4, cache="paged", block_size=4,
                           n_blocks=8)
    trainer = SimTrainer()
    trainer.params = params          # stub trainer republishes real params
    rl = RLConfig(batch_size=4, max_staleness=4, interruptible=True)
    ctl = AsyncRLController(engine=e, trainer=trainer,
                            prompt_stream=_Stream(), rl=rl,
                            timing=TimingModel(decode_step=lambda n: 0.01,
                                               prefill=lambda t: 1e-4 * t,
                                               train_step=lambda t: 0.1))
    hist = ctl.run(2)
    assert [h.version for h in hist] == [1, 2]
    # the loop may have pre-popped the next batch into its train slot
    assert ctl.buffer.total_consumed == 2 * 4 + len(ctl._train_batch or [])


# ---------------------------------------------------------------------------
# Multi-turn continuation (DESIGN.md §Environments and reward service)
# ---------------------------------------------------------------------------

def _mt_run(model, params, continuation, *, cache="ring", eos=tokenizer.EOS,
            interrupt_at=(), n_reqs=3, group=False, seed=0):
    eng = RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=4, prompt_len=8, max_gen_len=20, seed=seed, cache=cache,
        block_size=4, prefill_chunk=4, continuation=continuation,
        eos_id=eos))
    reqs = [{"rid": i, "prompt_id": 0 if group else i,
             "prompt": [1, 4, 5, 6] if group else [1, 4 + i, 5, 6],
             "answer": None} for i in range(n_reqs)]
    done, pending, step = {}, list(reqs), 0
    while len(done) < len(reqs):
        n = eng.admit(pending)
        pending = pending[n:]
        if step in interrupt_at:
            eng.update_weights(eng.params, eng.version)   # same weights
        for f in eng.step():
            done[f.rid] = f
        step += 1
        assert step < 3000, eng.stats()
    return eng, done


def _probe_eos(model, params, cache="ring"):
    """A token the seed-0 run actually samples early: using it as eos_id
    makes episodes end (and continuations fire) deterministically."""
    _, done = _mt_run(model, params, None, cache=cache)
    return done[0].response[3]


def test_continuation_requires_chunked_engine():
    cfg = _tiny()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(7))
    with pytest.raises(ValueError, match="prefill_chunk"):
        RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=2, prompt_len=8, max_gen_len=6,
            continuation=lambda f, t, b: None))


@pytest.mark.parametrize("cache", ["ring", "paged"])
def test_multiturn_continuation_appends_and_masks(cache):
    """An episode whose environment answers back continues in the SAME
    slot: env tokens land in the response with loss_mask 0, the turn
    count grows, and only the appended span is ever ingested
    (continuation_tokens == appended tokens — shared history is reused,
    not re-written)."""
    cfg = _tiny()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(7))
    eos = _probe_eos(model, params, cache=cache)
    EXTRA = [9, 10, 11]

    def hook(fin, turn, budget):
        assert fin.response[-1] == eos          # hook sees the full turn
        return list(EXTRA) if turn == 0 and budget > len(EXTRA) else None

    eng, done = _mt_run(model, params, hook, cache=cache, eos=eos,
                        group=True)
    st = eng.stats()
    assert st["continuations"] >= 1
    # THE pool-stats acceptance check: ingested continuation work is
    # exactly the appended spans — prompt/history blocks (shared by the
    # GRPO group in paged mode) are never re-written
    assert st["continuation_tokens"] == st["continuations"] * len(EXTRA)
    assert st["reprefill_tokens"] == 0
    assert st["prefill_tokens"] == 3 * 4        # admission prompts only
    if cache == "paged":
        assert st["prefix_reused_blocks"] > 0   # group sharing survived
    multi = [f for f in done.values() if f.turns > 1]
    assert multi
    for f in multi:
        # the env span sits in the response, loss-masked, logprob 0
        idx = next(i for i in range(len(f.response))
                   if f.response[i:i + len(EXTRA)] == EXTRA
                   and f.loss_mask[i] == 0.0)
        assert f.loss_mask[idx:idx + 3] == [0.0] * 3
        assert f.logprobs[idx:idx + 3] == [0.0] * 3
        assert sum(m == 0.0 for m in f.loss_mask) == 3
        assert len(f.loss_mask) == len(f.response)
    single = [f for f in done.values() if f.turns == 1]
    for f in single:
        assert f.loss_mask is None              # legacy shape untouched


@pytest.mark.parametrize("cache", ["ring", "paged"])
@pytest.mark.parametrize("family,extra", [
    ("dense", {}),
    ("hybrid", {"block_pattern": ("rec", "local"), "d_ff": 64,
                "local_window": 4}),
])
def test_multiturn_interrupt_identity(family, extra, cache):
    """Proposition-1 extension: a same-weights interrupt landing DURING
    a multi-turn episode (forcing a full re-ingest of the grown context)
    reproduces the uninterrupted trajectories bit-for-bit — the
    incremental continuation ingest wrote exactly the right cache/pool
    state."""
    if family != "dense" and cache == "paged":
        pytest.skip("paged needs attention KV (dense only here)")
    cfg = _tiny(family, **extra)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(7))
    eos = _probe_eos(model, params, cache=cache)

    def hook(fin, turn, budget):
        return [9, 10, 11] if turn == 0 and budget > 3 else None

    ea, a = _mt_run(model, params, hook, cache=cache, eos=eos)
    eb, b = _mt_run(model, params, hook, cache=cache, eos=eos,
                    interrupt_at=(6, 9))
    assert ea.continuations >= 1 and eb.interruptions == 2
    for rid in a:
        assert a[rid].response == b[rid].response, rid
        assert a[rid].turns == b[rid].turns
        assert a[rid].loss_mask == b[rid].loss_mask


# ---------------------------------------------------------------------------
# Decode fast paths (DESIGN.md §Fused decode tail, §Self-speculative decoding)
# ---------------------------------------------------------------------------

def _greedy_engine(cfg, cache, prefill_chunk=0, **kw):
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(7))
    return RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=4, prompt_len=8, max_gen_len=6, seed=3, temperature=0.0,
        cache=cache, block_size=4, prefill_chunk=prefill_chunk,
        rng="request" if prefill_chunk else "auto", **kw))


@pytest.mark.parametrize("cache", ["ring", "paged"])
@pytest.mark.parametrize("prefill_chunk", [0, 3])
@pytest.mark.parametrize("family,extra", [
    ("dense", {}),
    ("hybrid", {"block_pattern": ("rec", "local"), "d_ff": 64,
                "local_window": 4}),
])
def test_spec_greedy_matches_baseline(family, extra, cache, prefill_chunk):
    """The tentpole identity: greedy self-speculative decoding produces
    the SAME full token sequences as the plain engine on the same seed —
    speculation is a pure execution-schedule change (draft k-1 with the
    truncated model, verify in one chunk pass, commit the agreeing
    prefix), never a sampling change."""
    cfg = _tiny(family, n_layers=3, **extra)
    e1 = _greedy_engine(cfg, cache, prefill_chunk)
    e2 = _greedy_engine(cfg, cache, prefill_chunk, spec_decode=3)
    d1 = _run_to_completion(e1, _reqs(6))
    d2 = _run_to_completion(e2, _reqs(6))
    assert e2.spec_rounds > 0 and e2.drafted_tokens > 0
    for rid in d1:
        assert d1[rid].response == d2[rid].response, (family, cache)
        np.testing.assert_allclose(d1[rid].logprobs, d2[rid].logprobs,
                                   atol=1e-4)
    # every committed token is counted, and acceptance is a rate
    assert e2.accepted_tokens == e2.tokens_generated
    assert 0.0 <= e2.draft_acceptance_rate <= 1.0


@pytest.mark.parametrize("cache", ["ring", "paged"])
def test_spec_interrupt_mid_draft_is_identity(cache):
    """A same-weights interrupt landing BETWEEN the draft and verify
    phases discards the in-flight proposals (never the committed state),
    so trajectories still match the uninterrupted engine exactly."""
    cfg = _tiny("dense", n_layers=3)
    e1 = _greedy_engine(cfg, cache)
    d1 = _run_to_completion(e1, _reqs(5))

    e2 = _greedy_engine(cfg, cache, spec_decode=3)
    done, pending, step, mid_draft_hits = {}, _reqs(5), 0, 0
    while len(done) < 5:
        n = e2.admit(pending)
        pending = pending[n:]
        # interrupt the first few staged-but-unverified rounds (always
        # interrupting would starve commits forever — each discarded
        # round is redrafted on the next step)
        if e2.spec_pending and mid_draft_hits < 3:
            mid_draft_hits += 1
            e2.update_weights(e2.params, e2.version)
            assert not e2.spec_pending     # interrupt discarded the round
        for f in e2.step():
            done[f.rid] = f
        step += 1
        assert step < 500
    assert mid_draft_hits > 0
    for rid in d1:
        assert d1[rid].response == done[rid].response
        np.testing.assert_allclose(d1[rid].logprobs, done[rid].logprobs,
                                   atol=1e-4)


def test_fused_and_split_match_default_paged():
    """The fused single-dispatch step and the split two-dispatch
    baseline compose the identical jnp ops as the default paged path, so
    all three are bitwise-equal — and the dispatch counter proves the
    fused step really is ONE jitted call per decode step."""
    cfg = _tiny("dense")

    def run(**kw):
        model = build_model(cfg, remat=False)
        params = model.init(jax.random.key(7))
        eng = RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=4, prompt_len=8, max_gen_len=6, seed=3, cache="paged",
            block_size=4, **kw))
        return eng, _run_to_completion(eng, _reqs(6))

    e_def, d_def = run()
    e_fus, d_fus = run(fused_decode="fused")
    e_spl, d_spl = run(fused_decode="split")
    for rid in d_def:
        assert d_def[rid].response == d_fus[rid].response
        assert d_def[rid].response == d_spl[rid].response
        assert d_def[rid].logprobs == d_fus[rid].logprobs
        assert d_def[rid].logprobs == d_spl[rid].logprobs
    assert e_fus.decode_dispatches == e_def.decode_dispatches
    assert e_spl.decode_dispatches == 2 * e_def.decode_dispatches
    st = e_fus.stats()
    assert st["decode_dispatches"] == e_fus.decode_dispatches


def test_decode_fastpath_validation():
    cfg = _tiny("dense")
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(7))

    def make(**kw):
        return RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=2, prompt_len=8, max_gen_len=6, **kw))

    with pytest.raises(ValueError, match="paged"):
        make(fused_decode="fused")                     # ring + fused
    with pytest.raises(ValueError, match="fused_decode"):
        make(cache="paged", fused_decode="bogus")
    with pytest.raises(ValueError, match="temperature"):
        make(spec_decode=3)                            # sampling + spec
    with pytest.raises(ValueError, match=">= 2"):
        make(spec_decode=1, temperature=0.0)
    with pytest.raises(ValueError, match="one"):
        make(cache="paged", fused_decode="fused", spec_decode=3,
             temperature=0.0)
    with pytest.raises(ValueError, match="spec_draft_units"):
        make(spec_decode=3, temperature=0.0, spec_draft_units=99)


def test_spec_stats_surface():
    """stats() exposes the speculative counters the fleet liveness line
    and the decode_speed benchmark consume."""
    cfg = _tiny("dense", n_layers=3)
    eng = _greedy_engine(cfg, "paged", spec_decode=3)
    _run_to_completion(eng, _reqs(4))
    st = eng.stats()
    for key in ("decode_dispatches", "drafted_tokens", "accepted_tokens",
                "spec_rounds", "draft_acceptance_rate",
                "accepted_tokens_per_step"):
        assert key in st, key
    assert st["drafted_tokens"] > 0
    assert st["accepted_tokens"] == eng.tokens_generated
    assert st["accepted_tokens_per_step"] > 0.0


# ---- rollout run weights (DESIGN.md §Rollout run weights) -----------------

_CAST = {("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
         ("mlp", "w_up"), ("mlp", "w_gate"), ("mlp", "w_down"), ("head", "w")}


def _check_run_tree(eng, params, cast=_CAST):
    """``eng.run_params`` holds ``params``' dot weights in bf16 with the
    values of ``astype(bf16)``, and every other leaf as the same array."""
    import jax.numpy as jnp
    from repro.dist.sharding import path_names

    assert eng.params is params
    seen = set()
    for (path, run), (_, src) in zip(
            jax.tree_util.tree_flatten_with_path(eng.run_params)[0],
            jax.tree_util.tree_flatten_with_path(params)[0]):
        key = path_names(path)[-2:]
        if key in cast:
            seen.add(key)
            assert run.dtype == jnp.bfloat16, key
            np.testing.assert_array_equal(
                np.asarray(run), np.asarray(src.astype(jnp.bfloat16)))
        else:
            assert run is src, key
            assert run.dtype == jnp.float32, key
    assert seen == cast
    assert eng.stats()["run_weight_dtype"] == "bfloat16"


def test_run_weights_rebuilt_once_per_applied_version():
    """With the rule forced on, the engine keeps the f32 tree it was
    given as ``params`` and casts the dot weights once each time the
    weights change: at construction, ``place``, ``update_weights``, a
    deferred update applied by ``maybe_apply_pending`` and a streamed
    flip.  An update deferred behind in-flight requests casts nothing,
    and the engine still generates from the cast copy."""
    from jax.sharding import Mesh

    from repro.core.weights import encode_stream
    from repro.launch.disaggregated import host_weights
    from repro.models import model as model_mod

    cfg = _tiny()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(7))
    bump = lambda p, k: jax.tree.map(lambda x: x + 0.01 * k, p)  # noqa: E731
    with model_mod.use_run_weights(True):
        eng = RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=4, prompt_len=8, max_gen_len=6, cache="paged",
            block_size=4))
        casts = lambda: eng.stats()["run_weight_casts"]    # noqa: E731
        assert casts() == 1
        _check_run_tree(eng, params)

        eng.place(Mesh(np.array(jax.devices()[:1]), ("x",)))
        assert casts() == 2
        _check_run_tree(eng, eng.params)

        p1 = bump(params, 1)
        assert eng.update_weights(p1, 1)
        assert casts() == 3
        _check_run_tree(eng, p1)
        eng.update_weights(p1, 1)                  # the same tree again
        assert casts() == 3

        assert eng.admit(_reqs(2)) == 2
        p2 = bump(params, 2)
        assert not eng.update_weights(p2, 2, interruptible=False)
        assert casts() == 3                        # deferred: no cast
        _check_run_tree(eng, p1)
        done = {}
        while len(done) < 2:
            done.update({f.rid: f for f in eng.step()})
        assert all(f.versions == [1] * len(f.response) for f in done.values())
        assert eng.maybe_apply_pending()
        assert casts() == 4 and eng.version == 2
        _check_run_tree(eng, p2)

        p3 = bump(params, 3)
        stream = encode_stream(host_weights(p3), version=3,
                               base=host_weights(p2), base_version=2,
                               encoding="delta", chunk_elems=64)
        assert [eng.feed_weight_message(m) for m in stream][-1]
        assert casts() == 5 and eng.version == 3
        _check_run_tree(eng, eng.params)
        np.testing.assert_array_equal(
            np.asarray(eng.params["head"]["w"]), np.asarray(p3["head"]["w"]))


def test_run_weights_fused_tail_keeps_wo():
    """Under the fused decode tail the Pallas kernel reads ``attn/wo``:
    it stays f32 and shared, the other dot weights are cast."""
    from repro.models import model as model_mod

    cfg = _tiny()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(7))
    with model_mod.use_run_weights(True):
        eng = RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=4, prompt_len=8, max_gen_len=6, cache="paged",
            block_size=4, fused_decode="fused"))
    _check_run_tree(eng, params, _CAST - {("attn", "wo")})
    assert eng.stats()["run_weight_casts"] == 1


def test_run_weights_off_by_default_on_cpu():
    """Off the TPU the rule does not engage: the programs read
    ``params`` itself and no cast is counted."""
    _, params, eng = _engine(_tiny(), cache="paged", block_size=4)
    assert eng.run_params is params and eng.params is params
    eng.update_weights(jax.tree.map(lambda x: x + 1.0, params), 1)
    assert eng.run_params is eng.params
    st = eng.stats()
    assert st["run_weight_casts"] == 0 and st["run_weight_dtype"] is None


@pytest.mark.parametrize("platform,precision,on", [
    ("tpu", None, True), ("tpu", "default", True), ("tpu", "bfloat16", True),
    ("tpu", "highest", False), ("tpu", "float32", False),
    ("cpu", None, False), ("gpu", "default", False)])
def test_run_weight_rule(monkeypatch, platform, precision, on):
    """The copy is built only where a default f32 dot is already one
    bf16 pass: a TPU, at the default or bfloat16 matmul precision."""
    import contextlib

    from repro.models import model as model_mod

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    scope = (jax.default_matmul_precision(precision) if precision
             else contextlib.nullcontext())
    with scope:
        assert (model_mod.run_weight_dtype() is not None) is on
        with model_mod.use_run_weights(not on):
            assert (model_mod.run_weight_dtype() is not None) is (not on)
