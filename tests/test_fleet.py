"""Fleet executor: supervisor policy units (fast) and multi-process
integration (slow lane, real OS processes).

Fast tests exercise the scheduler's per-worker assignment/requeue
bookkeeping (DESIGN.md §Requeue semantics) and the shared liveness
diagnostics without spawning anything.  Slow tests spawn the real
fleet — simulator-stub workers for supervision/elastic behaviour and a
tiny real model for the trajectory-equivalence and kill-mid-ingest
acceptance criteria (DESIGN.md §Fleet runtime).
"""
import json
import os
import signal
import threading
import time

import pytest

from repro.configs.base import RLConfig
from repro.core import AsyncScheduler, FleetRuntime, ThreadedRuntime
from repro.core.fleet import WorkerHandle
from repro.core.runtime import Executor, RoleLiveness, format_liveness
from repro.core.simulator import SimEngine, SimPromptStream, SimTrainer
from repro.env.base import DelayEnv, Environment, Verdict

ANSWERS = 4


def _sched(*, eta=4, batch=8, answers=ANSWERS, prompt_len=8):
    rl = RLConfig(batch_size=batch, max_staleness=eta, interruptible=True)
    stream = SimPromptStream(prompt_len, answers_per_prompt=answers)
    return AsyncScheduler(prompt_stream=stream, rl=rl)


def _capture(sched):
    """Record every consumed trajectory (what actually trained)."""
    cap = []
    orig = sched.record_consumed

    def wrapper(batch):
        cap.extend(batch)
        return orig(batch)

    sched.record_consumed = wrapper
    return cap


# ---------------------------------------------------------------------------
# Fast: scheduler fleet bookkeeping (assignment, requeue, acks)
# ---------------------------------------------------------------------------

def test_requeue_is_idempotent_and_never_double_counts():
    # eta=0, B=4: Eq. 3 admits exactly 4 requests before version 1
    sched = _sched(eta=0, batch=4)
    reqs = sched.plan_admission(8)
    assert len(reqs) == 4
    sched.assign("w0", reqs)
    rids = [r["rid"] for r in reqs]
    assert sched.stal.n_submitted == 4
    assert sched.inflight_of("w0") == sorted(rids)
    assert sched.plan_admission(8) == []      # budget exhausted

    got = sched.requeue_worker("w0")
    assert [r["rid"] for r in got] == sorted(rids)
    assert sched.requeue_worker("w0") == []   # second requeue: no-op
    assert sched.requeued_total == 4
    assert sched.stal.n_submitted == 4        # counted exactly once

    # counted requeued work bypasses the Eq. 3 gate (it is already
    # inside N_r) — otherwise a crash at the staleness bound deadlocks
    again = sched.plan_admission(8)
    assert [r["rid"] for r in again] == sorted(rids)
    sched.assign("w1", again)
    assert sched.stal.n_submitted == 4
    assert sched.inflight_of("w1") == sorted(rids)
    assert sched.inflight_of("w0") == []


def test_acked_partial_returns_unadmitted_to_deferred_front():
    sched = _sched(eta=4, batch=4)
    reqs = sched.plan_admission(4)
    assert len(reqs) == 4
    sched.assign("w0", reqs)
    sched.acked("w0", reqs, 2, deferred=1)    # engine took 2, bounced 2
    assert sched.inflight_of("w0") == sorted(r["rid"] for r in reqs[:2])
    nxt = sched.plan_admission(2)             # re-offered first, in order
    assert [r["rid"] for r in nxt] == [r["rid"] for r in reqs[2:]]
    assert sched.requeued_total == 0          # ack-return is not a requeue
    assert sched.stal.n_submitted == 4


def test_finished_inflight_excludes_rid_from_requeue():
    sched = _sched(eta=4, batch=4)
    reqs = sched.plan_admission(3)
    sched.assign("w0", reqs)
    mid = reqs[1]["rid"]
    assert sched.finished_inflight(mid)
    assert not sched.finished_inflight(mid)   # already delivered
    got = sched.requeue_worker("w0")
    assert [r["rid"] for r in got] == sorted(
        [reqs[0]["rid"], reqs[2]["rid"]])


class _StubService:
    """saturated()-only stand-in for AsyncRewardService."""
    env = None

    def __init__(self):
        self.sat = False

    def bind(self, sink):
        pass

    def saturated(self):
        return self.sat


def test_saturated_delegates_and_backpressures_new_admissions():
    svc = _StubService()
    rl = RLConfig(batch_size=4, max_staleness=4, interruptible=True)
    sched = AsyncScheduler(prompt_stream=SimPromptStream(8, 4), rl=rl,
                           reward_service=svc)
    assert not sched.saturated()
    svc.sat = True
    assert sched.saturated()
    assert sched.plan_admission(4) == []      # no NEW work while saturated
    svc.sat = False
    reqs = sched.plan_admission(2)
    assert len(reqs) == 2
    sched.assign("w0", reqs)
    svc.sat = True                            # requeued work still flows:
    sched.requeue_worker("w0")                # it is already inside N_r
    assert len(sched.plan_admission(4)) == 2


# ---------------------------------------------------------------------------
# Fast: shared liveness diagnostics
# ---------------------------------------------------------------------------

def test_format_liveness_orders_dead_then_stalest_first():
    out = format_liveness([
        RoleLiveness("fresh", True, 0.1, "v=3"),
        RoleLiveness("dead", False, 5.0, ""),
        RoleLiveness("stale", True, 9.0, ""),
        RoleLiveness("neverbeat", False, None, ""),
    ])
    order = [out.index(f"role={r}") for r in
             ("neverbeat", "dead", "stale", "fresh")]
    assert order == sorted(order)
    assert "role=dead DEAD last-beat 5.0s ago" in out
    assert "never beat" in out
    assert "(v=3)" in out


def test_threaded_timeout_reports_per_role_liveness():
    rl = RLConfig(batch_size=64, max_staleness=4, interruptible=True)
    eng = SimEngine(n_slots=64, mean_len=200, max_len=2048,
                    prompt_len=64, seed=7)
    sched = AsyncScheduler(prompt_stream=SimPromptStream(64), rl=rl)
    sched.stal.n_submitted = 10 ** 9          # wedge admission: no batch
    rt = ThreadedRuntime(engine=eng, trainer=SimTrainer(), scheduler=sched)
    with pytest.raises(TimeoutError) as ei:
        rt.run(1, timeout=0.5)
    msg = str(ei.value)
    assert "unscored=" in msg
    assert "role=rollout" in msg and "role=trainer" in msg
    assert "last-beat" in msg or "never beat" in msg
    # the timeout post-mortem carries every diagnostic surface
    # (DESIGN.md §Flight-recorder protocol): weight-publication
    # counters, streaming-pickup counters, the flight-recorder tail
    assert "publication={" in msg and "'published'" in msg
    assert "stream=" in msg
    assert "flight-recorder tail:" in msg
    assert "train_step" in msg or "(empty)" in msg


def test_executor_protocol_covers_both_runtimes():
    sched = _sched()
    threaded = ThreadedRuntime(engine=SimEngine(n_slots=4, mean_len=10,
                                                max_len=32, prompt_len=8),
                               trainer=SimTrainer(), scheduler=sched)
    fleet = FleetRuntime(scheduler=_sched(),
                         engine_factory=sim_engine_factory,
                         engine_factory_kwargs={},
                         trainer_factory=sim_trainer_factory,
                         trainer_factory_kwargs={}, n_slots=4)
    assert isinstance(threaded, Executor)
    assert isinstance(fleet, Executor)


def test_fleet_refuses_to_contend_for_tpu_chips(monkeypatch):
    """On a TPU the fleet fails at construction, naming the counts,
    instead of starting workers that would hang on a held chip; workers
    pinned to the CPU are allowed."""
    import jax

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: ["chip0"])
    with pytest.raises(RuntimeError,
                       match=r"3 chip-holding worker processes \(2 rollout "
                             r"\+ 1 trainer\) on a host with 1 TPU chip"):
        _fleet(_sched())
    fleet = _fleet(_sched(), worker_env={"JAX_PLATFORMS": "cpu"})
    assert fleet.worker_env == {"JAX_PLATFORMS": "cpu"}


# ---------------------------------------------------------------------------
# Fast: supervisor failure path (no processes — fakes)
# ---------------------------------------------------------------------------

class _FakeProc:
    pid = 0

    def is_alive(self):
        return False

    def terminate(self):
        pass

    def kill(self):
        pass

    def join(self, timeout=None):
        pass


class _FakeTransport:
    raw = None

    def send(self, msg):
        raise OSError("peer gone")

    def recv(self, timeout=0.0):
        raise EOFError

    def close(self):
        pass


def test_fail_worker_is_idempotent_single_requeue():
    sched = _sched(eta=4, batch=4)
    rt = FleetRuntime(scheduler=sched,
                      engine_factory=sim_engine_factory,
                      engine_factory_kwargs={},
                      trainer_factory=sim_trainer_factory,
                      trainer_factory_kwargs={}, n_slots=4,
                      rollout_workers=1)
    rt._stop.set()                            # suppress the respawn leg
    h = WorkerHandle(worker_id="rollout-0", role="rollout",
                     proc=_FakeProc(), transport=_FakeTransport())
    h.state = "ready"
    rt.registry.add(h)
    reqs = sched.plan_admission(3)
    sched.assign("rollout-0", reqs)

    rt._fail_worker(h, reason="crashed")
    assert h.state == "dead"
    assert sched.requeued_total == 3
    assert rt._failures == 1
    # a second diagnosis (e.g. a salvaged 'error' message) is a no-op
    rt._fail_worker(h, reason="error")
    assert sched.requeued_total == 3
    assert rt._failures == 1
    assert len(rt.registry.events_of("worker-dead")) == 1


# ---------------------------------------------------------------------------
# Slow: real multi-process fleet over simulator stubs
# ---------------------------------------------------------------------------

class _SlowEngine:
    """SimEngine proxy that makes each decode step take wall time, so
    kill/drain windows are wide enough to hit deterministically."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay_s = delay_s

    def step(self):
        time.sleep(self._delay_s)
        return self._inner.step()

    def __getattr__(self, name):
        return getattr(self._inner, name)


def sim_engine_factory(*, n_slots=4, mean_len=12, max_len=32, prompt_len=8,
                       seed=0, slow_step_s=0.0):
    eng = SimEngine(n_slots=n_slots, mean_len=mean_len, max_len=max_len,
                    prompt_len=prompt_len, seed=seed)
    return _SlowEngine(eng, slow_step_s) if slow_step_s else eng


def sim_trainer_factory():
    return SimTrainer()


def _fleet(sched, **kw):
    defaults = dict(scheduler=sched, engine_factory=sim_engine_factory,
                    engine_factory_kwargs={"n_slots": 4},
                    trainer_factory=sim_trainer_factory,
                    trainer_factory_kwargs={}, n_slots=4, rollout_workers=2,
                    heartbeat_s=0.05, heartbeat_timeout=5.0)
    defaults.update(kw)
    return FleetRuntime(**defaults)


RUN_TIMEOUT = 240.0


@pytest.mark.slow
def test_fleet_sim_run_completes_and_counts():
    sched = _sched(eta=2, batch=8)
    cap = _capture(sched)
    # 50 ms steps: the run outlasts the second rollout worker's start-up
    # (a few seconds on a loaded host), which an instant run need not
    rt = _fleet(sched, engine_factory_kwargs={"n_slots": 4,
                                              "slow_step_s": 0.05})
    try:
        hist = rt.run(3, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
    assert [h.version for h in hist] == [1, 2, 3]
    assert len(cap) == 24
    rids = [t.rid for t in cap]
    assert len(set(rids)) == len(rids)        # nothing double-counted
    assert rt.duplicates_dropped == 0
    assert rt.respawns == 0
    assert len(rt.registry.events_of("register")) == 3  # 2 rollout + 1 trainer


@pytest.mark.slow
def test_fleet_survives_sigkill_and_requeues_inflight(tmp_path):
    sched = _sched(eta=4, batch=8)
    cap = _capture(sched)
    rt = _fleet(sched, flightrec_dir=str(tmp_path),
                engine_factory_kwargs={
        "n_slots": 4, "mean_len": 16, "max_len": 48, "slow_step_s": 0.05})
    killed = {}

    def killer():
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            for h in rt.registry.ready("rollout"):
                if h.beats > 0 and rt.sched.inflight_of(h.worker_id):
                    killed["pid"] = h.proc.pid
                    killed["worker_id"] = h.worker_id
                    os.kill(h.proc.pid, signal.SIGKILL)
                    return
            time.sleep(0.02)

    threading.Thread(target=killer, daemon=True).start()
    try:
        rt.run(3, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
    assert killed, "killer never found an in-flight worker"
    assert rt.respawns >= 1
    assert rt.requeued >= 1                   # the victim's slots came back
    assert len(cap) == 24                     # training still completed
    rids = [t.rid for t in cap]
    assert len(set(rids)) == len(rids)        # no rid trained twice
    assert rt.duplicates_dropped == 0
    dead = rt.registry.events_of("worker-dead")
    assert any(e["reason"] == "crashed" for e in dead)
    # SIGKILL post-mortem (DESIGN.md §Flight-recorder protocol): the
    # victim beat at least once before dying, so the supervisor holds a
    # nonempty copy of its recorder tail — shipped over heartbeats, it
    # survives the process — and dumped it to flightrec_dir on failure.
    victim = killed["worker_id"]
    tail = rt.flight_recorder(victim)
    assert len(tail) > 0
    kinds = {e[2] for e in tail.tail(256)}
    assert "start" in kinds                  # first heartbeat shipped it
    dump = tmp_path / f"{victim}-crashed.json"
    assert dump.exists()
    events = json.loads(dump.read_text())
    assert events and events[0]["kind"] == "start"
    assert any(e["worker"] == victim
               for e in rt.registry.events_of("flightrec-dump"))


@pytest.mark.slow
def test_slow_but_alive_worker_is_not_respawned():
    # step takes 5x the heartbeat timeout; the beat thread keeps beating
    sched = _sched(eta=4, batch=4, answers=2)
    rt = _fleet(sched, rollout_workers=1,
                engine_factory_kwargs={"n_slots": 2, "mean_len": 8,
                                       "max_len": 10, "slow_step_s": 0.25},
                heartbeat_timeout=0.05 * 20)  # 1s, << one 0.25s*len episode
    try:
        rt.run(1, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
    assert rt.respawns == 0
    assert rt.registry.events_of("worker-dead") == []


@pytest.mark.slow
def test_hung_worker_detected_as_hung_and_respawned():
    # SIGSTOP: the process stays alive but stops beating — the
    # supervisor must diagnose 'hung' and force it out (SIGKILL works
    # on stopped processes; SIGTERM would be deferred)
    sched = _sched(eta=4, batch=8)
    rt = _fleet(sched, engine_factory_kwargs={
        "n_slots": 4, "mean_len": 16, "max_len": 48, "slow_step_s": 0.05},
        heartbeat_timeout=1.0)
    stopped = {}

    def stopper():
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            for h in rt.registry.ready("rollout"):
                if h.beats > 5:
                    stopped["pid"] = h.proc.pid
                    os.kill(h.proc.pid, signal.SIGSTOP)
                    return
            time.sleep(0.02)

    threading.Thread(target=stopper, daemon=True).start()
    try:
        rt.run(2, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
    assert stopped, "stopper never found a beating worker"
    dead = rt.registry.events_of("worker-dead")
    assert any(e.get("hung") for e in dead)
    assert rt.respawns >= 1


class _AlwaysRight(Environment):
    name = "always-right"

    def verify(self, fin) -> Verdict:
        return Verdict(ok=True)


@pytest.mark.slow
def test_elastic_shrink_drains_gracefully_nothing_unscored_dropped():
    from repro.env.service import AsyncRewardService

    rl = RLConfig(batch_size=8, max_staleness=8, interruptible=True)
    svc = AsyncRewardService(DelayEnv(_AlwaysRight(), 0.10),
                             n_workers=1, max_backlog=4)
    sched = AsyncScheduler(
        prompt_stream=SimPromptStream(8, answers_per_prompt=ANSWERS),
        rl=rl, reward_service=svc)
    cap = _capture(sched)
    rt = _fleet(sched, rollout_workers=2, elastic=True, min_workers=1,
                elastic_interval=0.1,
                engine_factory_kwargs={"n_slots": 4, "mean_len": 10,
                                       "max_len": 16, "slow_step_s": 0.01})
    try:
        rt.run(3, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
        svc.close()
    assert rt.registry.events_of("shrink"), \
        "reward backlog never triggered a shrink"
    assert len(cap) == 24
    rids = [t.rid for t in cap]
    assert len(set(rids)) == len(rids)
    # graceful drain: everything any worker ever delivered got scored
    st = svc.stats()
    assert st["n_scored"] == st["n_submitted"]


# ---------------------------------------------------------------------------
# Slow: real tiny model — trajectory equivalence + kill mid-ingest
# ---------------------------------------------------------------------------

def _tiny_model_cfg():
    from repro.configs.base import ModelConfig
    from repro.data import tokenizer
    return ModelConfig(name="fleet-tiny", family="dense", n_layers=1,
                       d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                       vocab_size=tokenizer.VOCAB_SIZE)


def _tiny_rl(lr=0.0):
    # lr=0: the Adam update is exactly zero, so params are bitwise
    # stable across versions and per-request RNG makes every rid's
    # tokens a pure function of (seed, rid) — any executor, any
    # interleaving, any interrupt point produces identical trajectories
    return RLConfig(batch_size=4, answers_per_prompt=2, max_staleness=2,
                    interruptible=True, ppo_minibatches=1,
                    microbatch_token_budget=64, lr=lr,
                    max_prompt_len=16, max_gen_len=8)


def tiny_engine_factory(*, seed=0, n_slots=2, prefill_chunk=0):
    from repro.core.fleet import build_engine
    kwargs = dict(n_slots=n_slots, prompt_len=16, max_gen_len=8,
                  rng="request", prefill_chunk=prefill_chunk)
    if prefill_chunk:
        kwargs.update(cache="paged", block_size=8)
    return build_engine(model_cfg=_tiny_model_cfg(), seed=seed,
                        engine_kwargs=kwargs)


def tiny_trainer_factory(*, seed=0, lr=0.0):
    from repro.core.fleet import build_trainer
    return build_trainer(model_cfg=_tiny_model_cfg(), rl=_tiny_rl(lr),
                         seed=seed)


def _math_sched(rl):
    from repro.env import EnvPromptStream, MathEnv
    env = MathEnv(seed=3, max_operand=9)
    return AsyncScheduler(
        prompt_stream=EnvPromptStream(MathEnv(seed=3, max_operand=9),
                                      answers_per_prompt=2),
        rl=rl, env=env)


def _by_rid(cap):
    return {t.rid: (tuple(t.prompt_tokens), tuple(t.response_tokens))
            for t in cap}


_REF_CACHE = {}


def _threaded_reference(prefill_chunk=0, steps=2):
    """Consumed trajectories of a single-process ThreadedRuntime on the
    same seed/config (cached — both slow tests compare against it)."""
    if prefill_chunk not in _REF_CACHE:
        rl = _tiny_rl()
        sched = _math_sched(rl)
        cap = _capture(sched)
        rt = ThreadedRuntime(engine=tiny_engine_factory(
            prefill_chunk=prefill_chunk),
            trainer=tiny_trainer_factory(), scheduler=sched)
        rt.run(steps, timeout=RUN_TIMEOUT)
        _REF_CACHE[prefill_chunk] = _by_rid(cap)
    return _REF_CACHE[prefill_chunk]


def _real_fleet(prefill_chunk=0):
    rl = _tiny_rl()
    sched = _math_sched(rl)
    cap = _capture(sched)
    rt = FleetRuntime(
        scheduler=sched, engine_factory=tiny_engine_factory,
        engine_factory_kwargs={"prefill_chunk": prefill_chunk},
        trainer_factory=tiny_trainer_factory, trainer_factory_kwargs={},
        n_slots=2, rollout_workers=2, heartbeat_s=0.05,
        heartbeat_timeout=30.0)
    return rt, sched, cap


@pytest.mark.slow
def test_fleet_trajectories_match_threaded_same_seed():
    ref = _threaded_reference()
    rt, sched, cap = _real_fleet()
    try:
        rt.run(2, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
    got = _by_rid(cap)
    assert len(got) == 8                      # 2 steps x B=4
    common = set(ref) & set(got)
    assert len(common) >= 4                   # >= one full batch overlaps
    for rid in sorted(common):
        assert ref[rid] == got[rid], f"rid {rid} diverged"


@pytest.mark.slow
def test_fleet_kill_mid_ingest_requeues_and_matches_reference():
    # chunked prefill (8 chunks/request) keeps the ingest queue visibly
    # non-empty; the killer strikes while the victim is mid-ingest, so
    # the requeued request re-prefills from scratch on the replacement
    ref = _threaded_reference(prefill_chunk=2)
    rt, sched, cap = _real_fleet(prefill_chunk=2)
    killed = {}

    def killer():
        deadline = time.monotonic() + 200
        while time.monotonic() < deadline:
            for h in rt.registry.ready("rollout"):
                backlog = h.stats.get("ingest_backlog_tokens", 0)
                if backlog > 0 and rt.sched.inflight_of(h.worker_id):
                    killed["pid"] = h.proc.pid
                    killed["backlog"] = backlog
                    os.kill(h.proc.pid, signal.SIGKILL)
                    return
            time.sleep(0.005)

    threading.Thread(target=killer, daemon=True).start()
    try:
        rt.run(2, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
    assert killed, "killer never observed a mid-ingest worker"
    assert rt.requeued >= 1
    assert rt.respawns >= 1
    got = _by_rid(cap)
    rids = [t.rid for t in cap]
    assert len(set(rids)) == len(rids)        # requeue did not duplicate
    assert rt.duplicates_dropped == 0
    common = set(ref) & set(got)
    assert common
    for rid in sorted(common):
        assert ref[rid] == got[rid], f"rid {rid} diverged after requeue"


@pytest.mark.slow
def test_fleet_kill_mid_weight_stream_never_applies_torn_version():
    """SIGKILL a worker that has received some (not all) chunks of a
    publication stream (DESIGN.md §Torn-stream recovery): the partial
    version dies with the worker, its replacement bootstraps from the
    supervisor's full weights, and every delivered trajectory is
    bit-identical to the threaded reference — proof no torn partial
    version was ever decoded against.

    stream_chunk_elems=16 makes v1's base-free full stream thousands of
    chunks long and stream_chunks_per_step=1 feeds them one per worker
    loop, so 'mid-stream' outlasts a request's decode and the killer sees
    it through the 50 ms heartbeats (at 64 elements per chunk a stream
    fed while both workers sat idle ended between two beats in about
    half the runs)."""
    ref = _threaded_reference()
    rl = _tiny_rl()
    sched = _math_sched(rl)
    cap = _capture(sched)
    rt = FleetRuntime(
        scheduler=sched, engine_factory=tiny_engine_factory,
        engine_factory_kwargs={}, trainer_factory=tiny_trainer_factory,
        trainer_factory_kwargs={}, n_slots=2, rollout_workers=2,
        heartbeat_s=0.05, heartbeat_timeout=30.0,
        weight_stream="delta", stream_chunk_elems=16,
        stream_chunks_per_step=1)
    killed = {}

    def killer():
        deadline = time.monotonic() + 200
        while time.monotonic() < deadline:
            for h in rt.registry.ready("rollout"):
                chunks = h.stats.get("stream_chunks_received", 0)
                mid = h.stats.get("stream_active", 0)
                if chunks >= 1 and mid and rt.sched.inflight_of(h.worker_id):
                    killed["pid"] = h.proc.pid
                    killed["chunks"] = chunks
                    os.kill(h.proc.pid, signal.SIGKILL)
                    return
            time.sleep(0.002)

    threading.Thread(target=killer, daemon=True).start()
    try:
        rt.run(3, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
    assert killed, "killer never observed a worker mid-stream"
    assert killed["chunks"] >= 1
    rids = [t.rid for t in cap]
    assert len(set(rids)) == len(rids)        # nothing double-counted
    assert rt.duplicates_dropped == 0
    # requeue/respawn counts are timing-dependent (the victim may have
    # delivered everything it owed in the kill window — that path is
    # pinned by test_fleet_kill_mid_ingest_requeues_and_matches_reference);
    # the mid-stream invariant is trajectory identity:
    got = _by_rid(cap)
    common = set(ref) & set(got)
    assert common
    for rid in sorted(common):
        assert ref[rid] == got[rid], f"rid {rid} diverged after mid-stream kill"
