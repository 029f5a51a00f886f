"""Fleet executor: process-parallel rollout with supervised recovery.

The fleet runtime (core/fleet.py, DESIGN.md §Fleet runtime) runs N
rollout workers and M trainer replicas as OS processes under a
supervising parent.  This benchmark proves the three properties the
design claims, on a real (tiny) model:

  * **equivalence** — with per-request RNG and ``lr=0`` (bitwise-frozen
    params), every trajectory is a pure function of its request id, so
    a 2-worker fleet must reproduce the single-process
    ``ThreadedRuntime``'s trajectories exactly on the same seed —
    regardless of which worker generated which request, or where weight
    updates interrupted it (Prop. 1);
  * **kill** — SIGKILL one rollout worker mid-episode: the supervisor
    requeues its in-flight slots, respawns a replacement, and training
    completes with no trajectory lost or double-counted (DESIGN.md
    §Requeue semantics);
  * **throughput** — effective throughput of the 2-process fleet vs the
    threaded runtime on the same workload (a floor gate: process
    supervision + pipe transport must not collapse throughput; on
    multi-core hosts the GIL-free workers typically win).

One subprocess runs all three sections (2 fake host devices, hard
timeout — a fleet deadlock fails the lane fast instead of hanging it).
Results land in ``BENCH_fleet_overlap.json``; the gated metrics
(tools/check_bench.py) are ``equivalence.trajectories_identical``,
``kill.completed`` / ``kill.requeued`` / ``kill.duplicates`` /
``kill.lost`` and ``throughput_ratio``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import bench_path, emit, smoke_steps

DEVICES = 2
EQ_STEPS = 2            # equivalence window (B=4 each)
KILL_STEPS = 3
THR_STEPS = 4           # measured throughput window: wider than the Eq. 3
                        # budget (eta=2, B=4 -> <= 12 prebuffered), so the
                        # window always contains live generation
WARMUP_STEPS = 1        # excludes compile from the measured window
RUN_TIMEOUT = 600.0


def _cfg():
    from repro.configs.base import ModelConfig
    from repro.data import tokenizer
    return ModelConfig(name="bench-fleet", family="dense", n_layers=1,
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


def _fleet(sched, **kw):
    from repro.core import FleetRuntime
    defaults = dict(scheduler=sched, engine_factory=engine_factory,
                    engine_factory_kwargs={},
                    trainer_factory=trainer_factory,
                    trainer_factory_kwargs={}, n_slots=2, rollout_workers=2,
                    heartbeat_s=0.05, heartbeat_timeout=30.0)
    defaults.update(kw)
    return FleetRuntime(**defaults)


def _threaded(sched, lr: float = 0.0):
    from repro.core import ThreadedRuntime
    return ThreadedRuntime(engine=engine_factory(n_slots=4),
                           trainer=trainer_factory(lr=lr), scheduler=sched)


def _equivalence(steps: int):
    import time

    sched = _sched()
    ref_cap = _capture(sched)
    rt = _threaded(sched)
    rt.run(steps, timeout=RUN_TIMEOUT)
    ref = _by_rid(ref_cap)

    sched = _sched()
    cap = _capture(sched)
    frt = _fleet(sched)
    t0 = time.perf_counter()
    try:
        frt.run(steps, timeout=RUN_TIMEOUT)
    finally:
        frt.close()
    got = _by_rid(cap)
    common = sorted(set(ref) & set(got))
    return {
        "steps": steps,
        "n_reference": len(ref),
        "n_fleet": len(got),
        "n_common": len(common),
        "trajectories_identical": bool(
            common and all(ref[r] == got[r] for r in common)),
        "fleet_wall_s": round(time.perf_counter() - t0, 3),
    }


def _kill(steps: int):
    import signal
    import threading
    import time

    sched = _sched()
    cap = _capture(sched)
    rt = _fleet(sched)
    killed = {}

    def killer():
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            for h in rt.registry.ready("rollout"):
                if h.beats > 0 and rt.sched.inflight_of(h.worker_id):
                    killed["pid"] = h.proc.pid
                    os.kill(h.proc.pid, signal.SIGKILL)
                    return
            time.sleep(0.005)

    threading.Thread(target=killer, daemon=True).start()
    try:
        rt.run(steps, timeout=RUN_TIMEOUT)
    finally:
        rt.close()
    rids = [t.rid for t in cap]
    expected = steps * rt.rl.batch_size
    return {
        "steps": steps,
        "killed": bool(killed),
        "completed": bool(rt.version >= steps and killed),
        "requeued": rt.requeued,
        "respawns": rt.respawns,
        "duplicates": rt.duplicates_dropped + (len(rids) - len(set(rids))),
        "lost": expected - len(rids),
        "worker_dead_events": len(rt.registry.events_of("worker-dead")),
    }


def _throughput_one(kind: str, steps: int):
    import time

    sched = _sched(lr=1e-3)
    rt = _threaded(sched, lr=1e-3) if kind == "threaded" \
        else _fleet(sched, trainer_factory_kwargs={"lr": 1e-3})
    try:
        rt.run(WARMUP_STEPS, timeout=RUN_TIMEOUT)
        hist0 = len(rt.sched.history)
        t0 = time.perf_counter()
        rt.run(steps, timeout=RUN_TIMEOUT)
        wall = time.perf_counter() - t0
    finally:
        if kind == "fleet":
            rt.close()
    consumed = sum(h.n_tokens for h in rt.sched.history[hist0:])
    return {
        "versions": steps,
        "wall_s": round(wall, 3),
        "tokens_consumed": consumed,
        "effective_throughput_tok_s": round(consumed / wall, 2),
    }


def _child(eq_steps: int, kill_steps: int, thr_steps: int) -> None:
    import jax

    out = {"devices": len(jax.devices()),
           "equivalence": _equivalence(eq_steps),
           "kill": _kill(kill_steps),
           "threaded": _throughput_one("threaded", thr_steps),
           "fleet": _throughput_one("fleet", thr_steps)}
    print("BENCH_JSON=" + json.dumps(out), flush=True)


def main() -> None:
    eq_steps = smoke_steps(EQ_STEPS, 1)
    kill_steps = smoke_steps(KILL_STEPS, 2)
    thr_steps = smoke_steps(THR_STEPS, 1)
    env = dict(os.environ)
    # a CPU structure check on fake host devices: never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICES}"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.fleet_overlap", "--child",
         str(eq_steps), str(kill_steps), str(thr_steps)],
        capture_output=True, text=True, env=env, timeout=1800)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("BENCH_JSON=")][-1]
    rec = json.loads(line[len("BENCH_JSON="):])

    thr_fleet = rec["fleet"]["effective_throughput_tok_s"]
    thr_threaded = rec["threaded"]["effective_throughput_tok_s"]
    rec["throughput_ratio"] = round(thr_fleet / thr_threaded, 3) \
        if thr_threaded else None
    with open(bench_path("BENCH_fleet_overlap.json"), "w") as f:
        json.dump(rec, f, indent=2)

    us_per_version = (rec["fleet"]["wall_s"]
                      / max(rec["fleet"]["versions"], 1) * 1e6)
    emit("fleet_overlap_throughput", us_per_version,
         f"throughput_x{rec['throughput_ratio']:.2f}")
    emit("fleet_overlap_recovery",
         rec["kill"]["requeued"] * 1.0,
         f"identical_{rec['equivalence']['trajectories_identical']}"
         f"_lost_{rec['kill']['lost']}")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    else:
        main()
