"""Streaming delta weight publication: identity, tokens lost, latency.

The streaming publication path (core/weights.py, DESIGN.md §Streaming
weight publication) ships each trainer update as a sequence of per-leaf
delta chunks that the rollout engine applies under a version fence
(DESIGN.md §Version fence): chunks for later layers decode and stage
host→device WHILE the engine keeps generating under the last complete
version, and the flip to the new version is a single ordinary
``update_weights`` once the stream completes.  This benchmark proves the
four properties the design claims, on a real (tiny) model:

  * **identity** — unquantized streaming is bit-for-bit
    trajectory-identical to a monolithic full-tree update applied at the
    same step boundary, across the engine matrix {ring, paged} x
    {monolithic, chunked prefill} (XOR deltas are exact for every dtype;
    the fence confines all stream effects to the flip step);
  * **stall** — under a fixed transport budget of ONE chunk per engine
    step opportunity, a monolithic publication occupies the engine for
    the full tree's chunk count (the generation pool stalls, as in the
    paper's Fig. 6b non-interruptible baseline), while the streamed
    publication feeds one chunk per opportunity alongside decoding and
    loses (here) zero tokens — tokens-lost-per-update and
    publication-to-pickup latency both drop by the full/delta chunk
    ratio, at no throughput cost.  All numbers in this section are
    deterministic (fixed schedule, no threads) and gated at zero drift;
  * **quantized** — ``delta-q`` (int8 + per-chunk scale) decodes within
    the stream's own declared tolerance, and IS lossy (the exact-XOR
    path is what the identity section runs);
  * **runtimes** — the real executors reproduce the section-level
    claims: ``ThreadedRuntime(weight_stream="delta")`` matches the full
    publication path trajectory-for-trajectory (lr=0 frozen params), and
    a fleet rollout worker SIGKILLed MID-STREAM leaves a fleet that
    still completes with zero lost/duplicated trajectories and
    bit-identical outputs — the torn partial version is discarded, never
    applied (DESIGN.md §Torn-stream recovery).

One subprocess runs every section (2 fake host devices, hard timeout).
Results land in ``BENCH_weight_stream.json``; the gated metrics
(tools/check_bench.py) are the identity booleans, the stall section's
zero-drift token/latency numbers, ``stall.tokens_lost_ratio`` and the
fleet-kill recovery fields.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import bench_path, emit, smoke_steps

DEVICES = 2
RUN_TIMEOUT = 600.0

# identity section: flip boundary + decode window (fixed, deterministic)
IDENT_FLIP_AT = 6
IDENT_STEPS = 60

# stall section: S decode opportunities, publications at fixed indices,
# transport budget 1 chunk/opportunity (fixed even in smoke mode: the
# whole section is a few hundred tiny decode steps and its numbers are
# gated at zero drift, so smoke must reproduce them exactly)
STALL_OPPS = 120
STALL_PUBLISH_AT = (20, 70)
STALL_CHUNK_ELEMS = 8192

THR_STEPS = 2
KILL_STEPS = 3


def _cfg():
    from repro.configs.base import ModelConfig
    from repro.data import tokenizer
    return ModelConfig(name="bench-wstream", family="dense", n_layers=1,
                       d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                       vocab_size=tokenizer.VOCAB_SIZE)


def _rl(lr: float = 0.0):
    from repro.configs.base import RLConfig
    return RLConfig(batch_size=4, answers_per_prompt=2, max_staleness=2,
                    interruptible=True, ppo_minibatches=1,
                    microbatch_token_budget=64, lr=lr,
                    max_prompt_len=16, max_gen_len=8)


# module-level so multiprocessing spawn can pickle them by reference
def engine_factory(*, seed: int = 0, n_slots: int = 2):
    from repro.core.fleet import build_engine
    return build_engine(model_cfg=_cfg(), seed=seed,
                        engine_kwargs=dict(n_slots=n_slots, prompt_len=16,
                                           max_gen_len=8, rng="request"))


def trainer_factory(*, seed: int = 0, lr: float = 0.0):
    from repro.core.fleet import build_trainer
    return build_trainer(model_cfg=_cfg(), rl=_rl(lr), seed=seed)


def _sched(lr: float = 0.0):
    from repro.core import AsyncScheduler
    from repro.env import EnvPromptStream, MathEnv
    return AsyncScheduler(
        prompt_stream=EnvPromptStream(MathEnv(seed=3, max_operand=9),
                                      answers_per_prompt=2),
        rl=_rl(lr), env=MathEnv(seed=3, max_operand=9))


def _capture(sched):
    cap = []
    orig = sched.record_consumed

    def wrapper(batch):
        cap.extend(batch)
        return orig(batch)

    sched.record_consumed = wrapper
    return cap


def _by_rid(cap):
    return {t.rid: (tuple(t.prompt_tokens), tuple(t.response_tokens))
            for t in cap}


# ---- engine-level plumbing (identity + stall sections) ----------------------
def _model_and_params(seed: int = 0):
    import jax

    from repro.models.model import build_model
    model = build_model(_cfg(), remat=False)
    return model, model.init(jax.random.key(seed))


def _perturb(params, seed: int):
    """A REAL weight update, deterministic and sparse: every third float
    leaf moves by small gaussian noise (sparse so the delta stream is
    much smaller than the full tree — the common case one PPO step in)."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(params)
    key = jax.random.key(1000 + seed)
    out = []
    for i, leaf in enumerate(leaves):
        if i % 3 == 0 and jnp.issubdtype(leaf.dtype, jnp.floating):
            k = jax.random.fold_in(key, i)
            out.append(leaf + 1e-3 * jax.random.normal(k, leaf.shape,
                                                       leaf.dtype))
        else:
            out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def _requests(n: int):
    return [{"rid": i, "prompt_id": i,
             "prompt": [2 + (7 * i + j) % 50 for j in range(8)],
             "answer": None} for i in range(n)]


def _engine(model, params, *, cache: str, prefill_chunk: int,
            max_gen_len: int = 16, n_slots: int = 4, eos_id: int = -1):
    from repro.core.config import EngineConfig
    from repro.core.rollout import RolloutEngine
    return RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=n_slots, prompt_len=16, max_gen_len=max_gen_len, seed=7,
        eos_id=eos_id, cache=cache, prefill_chunk=prefill_chunk,
        rng="request"))


def _identity_one(model, params0, params1_dev, msgs, *, cache: str,
                  prefill_chunk: int):
    """One (cache, prefill) config: run the monolithic-update baseline
    and the streamed run with the flip at the SAME step boundary; return
    per-rid (prompt, response, logprobs) for exact comparison."""
    import math

    def run(streamed: bool):
        eng = _engine(model, params0, cache=cache,
                      prefill_chunk=prefill_chunk)
        eng.admit(_requests(4))
        done = []
        pending = list(msgs)
        per_step = max(1, math.ceil((len(pending) - 1) / IDENT_FLIP_AT))
        for step in range(IDENT_STEPS):
            if streamed:
                if step < IDENT_FLIP_AT:
                    # chunks apply under decode of the old version: the
                    # fence keeps them out of the trajectories
                    for _ in range(per_step):
                        if len(pending) > 1:      # hold StreamEnd
                            eng.feed_weight_message(pending.pop(0))
                elif step == IDENT_FLIP_AT:
                    while pending:                # End included -> flip
                        eng.feed_weight_message(pending.pop(0))
                    assert eng.version == 1, eng.version
            elif step == IDENT_FLIP_AT:
                eng.update_weights(params1_dev, 1)
            done.extend(eng.step())
            if not eng.n_active:
                break
        return {f.rid: (tuple(f.prompt), tuple(f.response),
                        tuple(f.logprobs)) for f in done}

    base = run(streamed=False)
    stream = run(streamed=True)
    return {
        "n_finished": len(base),
        "n_finished_streamed": len(stream),
        "identical": bool(len(base) == 4 and base == stream),
    }


def _identity():
    import jax

    from repro.core.weights import encode_stream
    from repro.launch.disaggregated import host_weights

    model, params0 = _model_and_params()
    params1 = _perturb(params0, 1)
    stream = encode_stream(host_weights(params1), version=1,
                           base=host_weights(params0), base_version=0,
                           encoding="delta", chunk_elems=512)
    msgs = list(stream)
    params1_dev = jax.tree.map(jax.numpy.asarray, params1)
    out = {"stream_messages": len(msgs)}
    for cache in ("ring", "paged"):
        for pc, label in ((0, "monolithic"), (4, "chunked")):
            out[f"{cache}_{label}"] = _identity_one(
                model, params0, params1_dev, msgs, cache=cache,
                prefill_chunk=pc)
    out["all_identical"] = all(
        v["identical"] for k, v in out.items() if isinstance(v, dict))
    return out


def _stall():
    """Deterministic stall model, fixed transport budget of ONE chunk
    per decode opportunity.  Monolithic publication: the engine is
    occupied for the full tree's chunk count before it can flip (C_full
    stalled opportunities, G slots -> C_full*G tokens lost per update).
    Streamed: one chunk feeds per opportunity ALONGSIDE the decode step
    and the engine flips as soon as the (much shorter) delta stream
    completes.  A reference run with no updates bounds the token budget;
    everything is single-threaded and schedule-fixed, so the gate holds
    these numbers at zero drift."""
    import jax

    from repro.core.weights import encode_stream
    from repro.launch.disaggregated import host_weights

    model, params0 = _model_and_params()
    versions = [params0]
    for u in range(len(STALL_PUBLISH_AT)):
        versions.append(_perturb(versions[-1], u + 1))
    hosts = [host_weights(p) for p in versions]
    full_chunks = [encode_stream(hosts[u + 1], version=u + 1, base=None,
                                 chunk_elems=STALL_CHUNK_ELEMS).n_chunks
                   for u in range(len(STALL_PUBLISH_AT))]
    delta_streams = [encode_stream(hosts[u + 1], version=u + 1,
                                   base=hosts[u], base_version=u,
                                   encoding="delta",
                                   chunk_elems=STALL_CHUNK_ELEMS)
                     for u in range(len(STALL_PUBLISH_AT))]
    n_updates = len(STALL_PUBLISH_AT)

    def fresh():
        eng = _engine(model, params0, cache="ring", prefill_chunk=0,
                      max_gen_len=STALL_OPPS + 8)
        eng.admit(_requests(4))
        return eng

    # reference: every opportunity decodes, no publication
    ref = fresh()
    for _ in range(STALL_OPPS):
        ref.step()

    # monolithic: each publication occupies C_full opportunities
    # (transfer at 1 chunk/opportunity, applied whole) before the flip
    full = fresh()
    stall_left = 0
    pending_flip = None
    schedule = dict(zip(STALL_PUBLISH_AT, range(1, n_updates + 1)))
    flip_opps = []
    for opp in range(STALL_OPPS):
        if opp in schedule:
            u = schedule[opp]
            stall_left = full_chunks[u - 1]
            pending_flip = u
        if stall_left > 0:
            stall_left -= 1
            if stall_left == 0 and pending_flip is not None:
                full.update_weights(
                    jax.tree.map(jax.numpy.asarray, versions[pending_flip]),
                    pending_flip)
                flip_opps.append(opp)
                pending_flip = None
            continue                      # the stalled opportunity
        full.step()

    # streamed: one chunk per opportunity feeds alongside the decode
    delta = fresh()
    pending = []
    delta_flip_opps = []
    publish_opps = {}
    for opp in range(STALL_OPPS):
        if opp in schedule:
            u = schedule[opp]
            pending.extend(delta_streams[u - 1])
            publish_opps[u] = opp
        if pending:
            flipped = delta.feed_weight_message(pending.pop(0))
            if flipped:
                delta_flip_opps.append(opp)
        delta.step()
    assert delta.version == n_updates, delta.version
    assert full.version == n_updates, full.version

    lost_full = ref.tokens_generated - full.tokens_generated
    lost_delta = ref.tokens_generated - delta.tokens_generated
    delta_latency = [delta_flip_opps[u - 1] - publish_opps[u]
                     for u in schedule.values()]
    return {
        "opportunities": STALL_OPPS,
        "updates": n_updates,
        "ref_tokens": int(ref.tokens_generated),
        "full_tokens": int(full.tokens_generated),
        "delta_tokens": int(delta.tokens_generated),
        "chunks_full_per_update": sum(full_chunks) / n_updates,
        "chunks_delta_per_update": sum(s.n_chunks for s in delta_streams)
        / n_updates,
        "tokens_lost_full_per_update": lost_full / n_updates,
        "tokens_lost_delta_per_update": lost_delta / n_updates,
        "tokens_lost_ratio": lost_full / max(lost_delta, 1),
        "throughput_ratio": round(delta.tokens_generated
                                  / max(full.tokens_generated, 1), 3),
        # publication -> pickup, in decode opportunities: monolithic
        # waits out the whole transfer; streamed flips at stream end
        "full_latency_steps": sum(full_chunks) / n_updates,
        "delta_latency_steps": sum(delta_latency) / n_updates,
    }


def _quantized():
    import numpy as np

    from repro.core.weights import StreamDecoder, encode_stream, tree_items
    from repro.launch.disaggregated import host_weights

    model, params0 = _model_and_params()
    host0 = host_weights(params0)
    host1 = host_weights(_perturb(params0, 1))
    exact = encode_stream(host1, version=1, base=host0, base_version=0,
                          encoding="delta", chunk_elems=2048)
    q = encode_stream(host1, version=1, base=host0, base_version=0,
                      encoding="delta-q", chunk_elems=2048)
    dec = StreamDecoder(host0, 0)
    out = None
    for msg in q:
        out = dec.feed(msg) or out
    assert out is not None and out[0] == 1
    want = dict(tree_items(host1))
    err = max(float(np.max(np.abs(np.asarray(got) - want[path])))
              if np.asarray(got).size else 0.0
              for path, got in tree_items(out[1]))
    tol = q.tolerance()
    return {
        "max_abs_error": err,
        "declared_tolerance": tol,
        "within_tolerance": bool(err <= tol * (1 + 1e-6)),
        "lossy": bool(err > 0.0),
        "exact_stream_bytes": exact.nbytes(),
        "quantized_stream_bytes": q.nbytes(),
        "bytes_ratio": round(exact.nbytes() / max(q.nbytes(), 1), 3),
    }


def _threaded(sched, weight_stream: str = "full"):
    from repro.core import ThreadedRuntime
    return ThreadedRuntime(engine=engine_factory(n_slots=4),
                           trainer=trainer_factory(), scheduler=sched,
                           weight_stream=weight_stream,
                           stream_chunk_elems=512)


def _threaded_identity(steps: int):
    """ThreadedRuntime full vs delta publication on lr=0 frozen params:
    per-request RNG makes every trajectory a pure function of (seed,
    rid, params), so the two publication transports must produce
    identical trajectories on the common request ids."""
    sched = _sched()
    ref_cap = _capture(sched)
    rt = _threaded(sched, "full")
    rt.run(steps, timeout=RUN_TIMEOUT)
    ref = _by_rid(ref_cap)

    sched = _sched()
    cap = _capture(sched)
    srt = _threaded(sched, "delta")
    srt.run(steps, timeout=RUN_TIMEOUT)
    got = _by_rid(cap)
    common = sorted(set(ref) & set(got))
    ss = srt.engine.stream_stats()
    return {
        "steps": steps,
        "n_common": len(common),
        "trajectories_identical": bool(
            common and all(ref[r] == got[r] for r in common)),
        "streams_completed": ss["streams_completed"],
        "streams_torn": ss["streams_torn"],
        "publication": sched.publication_stats(),
    }


def _fleet_kill(steps: int):
    """SIGKILL a fleet rollout worker MID-STREAM (the first publication
    is base-free, so at stream_chunk_elems=64 it is hundreds of chunk
    messages fed one per engine loop — a wide kill window).  The fleet
    must requeue the victim's slots, respawn, resynchronize the
    replacement with a full tree at registration, and finish with
    trajectories bit-identical to a single-process reference — proof no
    torn partial version was ever applied (DESIGN.md §Torn-stream
    recovery)."""
    import signal
    import threading
    import time

    from repro.core import FleetRuntime

    sched = _sched()
    ref_cap = _capture(sched)
    rt = _threaded(sched, "full")
    rt.run(steps, timeout=RUN_TIMEOUT)
    ref = _by_rid(ref_cap)

    sched = _sched()
    cap = _capture(sched)
    frt = FleetRuntime(scheduler=sched, engine_factory=engine_factory,
                       engine_factory_kwargs={},
                       trainer_factory=trainer_factory,
                       trainer_factory_kwargs={}, n_slots=2,
                       rollout_workers=2, heartbeat_s=0.05,
                       heartbeat_timeout=30.0, weight_stream="delta",
                       stream_chunk_elems=64, stream_chunks_per_step=1)
    killed = {}

    def killer():
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and not killed:
            for h in frt.registry.ready("rollout"):
                if (h.stats.get("stream_chunks_received", 0) >= 1
                        and frt.sched.inflight_of(h.worker_id)):
                    killed["pid"] = h.proc.pid
                    killed["chunks_fed"] = h.stats["stream_chunks_received"]
                    os.kill(h.proc.pid, signal.SIGKILL)
                    return
            time.sleep(0.005)

    threading.Thread(target=killer, daemon=True).start()
    try:
        frt.run(steps, timeout=RUN_TIMEOUT)
    finally:
        frt.close()
    got = _by_rid(cap)
    rids = [t.rid for t in cap]
    common = sorted(set(ref) & set(got))
    expected = steps * frt.rl.batch_size
    return {
        "steps": steps,
        "killed": bool(killed),
        "chunks_fed_at_kill": killed.get("chunks_fed", 0),
        "completed": bool(frt.version >= steps and killed),
        "requeued": frt.requeued,
        "respawns": frt.respawns,
        "duplicates": frt.duplicates_dropped + (len(rids) - len(set(rids))),
        "lost": expected - len(rids),
        "n_common": len(common),
        "trajectories_identical": bool(
            common and all(ref[r] == got[r] for r in common)),
    }


def _child(thr_steps: int, kill_steps: int) -> None:
    import jax

    out = {"devices": len(jax.devices()),
           "identity": _identity(),
           "stall": _stall(),
           "quantized": _quantized(),
           "threaded": _threaded_identity(thr_steps),
           "fleet_kill": _fleet_kill(kill_steps)}
    print("BENCH_JSON=" + json.dumps(out), flush=True)


def main() -> None:
    thr_steps = smoke_steps(THR_STEPS, 1)
    kill_steps = smoke_steps(KILL_STEPS, 2)
    env = dict(os.environ)
    # a CPU structure check on fake host devices: never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICES}"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.weight_stream", "--child",
         str(thr_steps), str(kill_steps)],
        capture_output=True, text=True, env=env, timeout=1800)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("BENCH_JSON=")][-1]
    rec = json.loads(line[len("BENCH_JSON="):])
    with open(bench_path("BENCH_weight_stream.json"), "w") as f:
        json.dump(rec, f, indent=2)

    st = rec["stall"]
    emit("weight_stream_stall",
         st["tokens_lost_full_per_update"],
         f"lost_ratio_x{st['tokens_lost_ratio']:.1f}"
         f"_latency_{st['delta_latency_steps']:.0f}"
         f"of{st['full_latency_steps']:.0f}steps")
    emit("weight_stream_identity",
         rec["identity"]["stream_messages"] * 1.0,
         f"identical_{rec['identity']['all_identical']}"
         f"_killmid_{rec['fleet_kill']['trajectories_identical']}")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]), int(sys.argv[3]))
    else:
        main()
