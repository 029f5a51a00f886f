"""Reward-verification overlap: async scoring keeps throughput flat
while synchronous scoring degrades with verifier latency.

AReaL's reward service is the fourth system component (Section 4.1):
its verification latency is pipelined behind generation.  This
benchmark injects a controlled verifier latency (``DelayEnv``) into the
threaded runtime and measures effective throughput two ways over the
same fixed window:

  * **sync**  — ``reward_workers = 0``: every finished trajectory is
    verified inline on the rollout thread (the scheduler's synchronous
    environment path), so each verification stalls every decoding slot
    for the full injected latency;
  * **async** — an ``AsyncRewardService`` pool scores off the rollout
    thread (DESIGN.md §Environments and reward service): collection is
    enqueue-only and verification overlaps decoding, so throughput
    stays ~flat at the same injected latency.

Both runs execute in one subprocess with 4 fake host devices (the real
disaggregated submesh split), 2 warm-up versions excluded (first
weight-pickup compiles the full-width re-prefill, see
benchmarks/async_overlap.py), and identical seeds/workloads.

A second section drives the CODE environment end-to-end on the same
tiny pipeline — generated text executed against unit tests in the
restricted subprocess sandbox — so the sandbox runs in CI smoke
(``code_env.completed``).

Results land in ``BENCH_reward_overlap.json``; the gated metrics
(tools/check_bench.py) are ``throughput_ratio`` (async/sync >= 1.5x at
the injected latency), ``async.backlog_bounded`` and
``code_env.completed``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import bench_path, emit

DEVICES = 4
STEPS = 4               # measured versions (fixed window, both modes)
WARMUP_STEPS = 2        # excludes first-compile incl. active-slot re-prefill
LATENCY_S = 0.08        # injected verification latency per trajectory
WORKERS = 4
BACKLOG = 64


def _build(mode: str, seed: int = 0):
    """The async_overlap tiny balanced pipeline, with scoring routed
    through a DelayEnv-wrapped math environment: inline (sync) or via
    the reward-worker pool (async)."""
    import jax

    from repro.configs.base import ModelConfig, RLConfig
    from repro.core import (AsyncScheduler, EngineConfig, PPOTrainer,
                            RolloutEngine, ThreadedRuntime)
    from repro.data import tokenizer
    from repro.env import (AsyncRewardService, DelayEnv, EnvPromptStream,
                           MathEnv)
    from repro.launch.train import _place_disaggregated
    from repro.models.model import build_model

    cfg = ModelConfig(name="bench-reward", family="dense", n_layers=2,
                      d_model=48, n_heads=4, n_kv_heads=2, d_ff=96,
                      vocab_size=tokenizer.VOCAB_SIZE)
    rl = RLConfig(batch_size=16, answers_per_prompt=4, max_staleness=4,
                  interruptible=True, ppo_minibatches=2,
                  microbatch_token_budget=128, lr=1e-3,
                  max_prompt_len=16, max_gen_len=16)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(seed))
    engine = RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=8, prompt_len=16, max_gen_len=16, seed=seed))
    trainer = PPOTrainer(model, rl, params)
    env = DelayEnv(MathEnv(seed=seed, max_operand=9), LATENCY_S)
    service = None
    if mode == "async":
        service = AsyncRewardService(env, n_workers=WORKERS,
                                     max_backlog=BACKLOG)
    sched = AsyncScheduler(prompt_stream=EnvPromptStream(env, 4), rl=rl,
                           env=env, reward_service=service)
    roll_mesh = None
    if len(jax.devices()) > 1:
        roll_mesh, _ = _place_disaggregated(engine, trainer, 0.25)
    rt = ThreadedRuntime(engine=engine, trainer=trainer, scheduler=sched,
                         rollout_mesh=roll_mesh)
    return rt, service


def _measure(mode: str, steps: int, seed: int = 0):
    import time

    rt, service = _build(mode, seed)
    rt.run(WARMUP_STEPS, timeout=600)        # compiles outside the window
    v0 = rt.trainer.version
    hist0 = len(rt.history)
    t0 = time.perf_counter()
    rt.run(steps, timeout=600)
    wall = time.perf_counter() - t0
    consumed = sum(h.n_tokens for h in rt.history[hist0:])
    rec = {
        "mode": mode,
        "versions": rt.trainer.version - v0,
        "wall_s": round(wall, 3),
        "tokens_consumed": consumed,
        "effective_throughput_tok_s": round(consumed / wall, 2),
        "unscored_at_end": rt.sched.pending_rewards(),
    }
    if service is not None:
        st = service.stats()
        rec["reward_workers"] = st["n_workers"]
        rec["n_scored"] = st["n_scored"]
        rec["backlog_peak"] = st["backlog_peak"]
        rec["verify_mean_s"] = round(
            st["per_env"]["delay(math)"]["mean_s"], 4)
        # bounded backlog: admission backpressure caps unscored work at
        # max_backlog plus the generations already in flight (slots)
        rec["backlog_bounded"] = (st["backlog_peak"]
                                  <= st["max_backlog"] + rt.engine.n_slots)
        assert service.close(), "reward workers failed to drain"
    return rec


def _code_env(seed: int = 0):
    """Drive the CODE environment through the same stack: one PPO
    version whose every trajectory was verified by the subprocess
    sandbox on reward workers (the CI-smoke sandbox exercise)."""
    import jax

    from repro.configs.base import ModelConfig, RLConfig
    from repro.core import (AsyncScheduler, EngineConfig, PPOTrainer,
                            RolloutEngine, ThreadedRuntime)
    from repro.data import tokenizer
    from repro.env import AsyncRewardService, CodeEnv, EnvPromptStream

    from repro.models.model import build_model

    cfg = ModelConfig(name="bench-code", family="dense", n_layers=2,
                      d_model=48, n_heads=4, n_kv_heads=2, d_ff=96,
                      vocab_size=tokenizer.VOCAB_SIZE)
    rl = RLConfig(batch_size=8, answers_per_prompt=2, max_staleness=4,
                  interruptible=True, ppo_minibatches=2,
                  microbatch_token_budget=128, lr=1e-3,
                  max_prompt_len=56, max_gen_len=12)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(seed))
    engine = RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=4, prompt_len=56, max_gen_len=12, seed=seed))
    env = CodeEnv(seed=seed, timeout_s=2.0)
    service = AsyncRewardService(env, n_workers=2, max_backlog=16)
    sched = AsyncScheduler(prompt_stream=EnvPromptStream(env, 2), rl=rl,
                           reward_service=service)
    rt = ThreadedRuntime(engine=engine, trainer=PPOTrainer(model, rl, params),
                         scheduler=sched)
    rt.run(1, timeout=600)
    st = service.stats()
    drained = service.close()
    scored = st["n_scored"]
    return {
        "completed": bool(rt.trainer.version >= 1 and drained
                          and len(rt.history) >= 1),
        "scored": scored,
        "sandbox_verifications": st["per_env"].get("code", {}).get("n", 0),
        "verify_mean_s": round(
            st["per_env"].get("code", {}).get("mean_s", 0.0), 4),
        "accuracy": rt.reward.accuracy,
    }


def _child(steps: int) -> None:
    import jax

    out = {"devices": len(jax.devices()), "steps": steps,
           "injected_latency_s": LATENCY_S,
           "sync": _measure("sync", steps),
           "async": _measure("async", steps),
           "code_env": _code_env()}
    print("BENCH_JSON=" + json.dumps(out), flush=True)


def main() -> None:
    steps = STEPS
    env = dict(os.environ)
    # a CPU structure check on fake host devices: never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICES}"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.reward_overlap",
         "--child", str(steps)],
        capture_output=True, text=True, env=env, timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("BENCH_JSON=")][-1]
    rec = json.loads(line[len("BENCH_JSON="):])

    thr_async = rec["async"]["effective_throughput_tok_s"]
    thr_sync = rec["sync"]["effective_throughput_tok_s"]
    rec["throughput_ratio"] = round(thr_async / thr_sync, 3) if thr_sync \
        else None
    with open(bench_path("BENCH_reward_overlap.json"), "w") as f:
        json.dump(rec, f, indent=2)

    us_per_version = (rec["async"]["wall_s"]
                      / max(rec["async"]["versions"], 1) * 1e6)
    emit("reward_overlap_async", us_per_version,
         f"throughput_x{rec['throughput_ratio']:.2f}")
    emit("reward_overlap_code_env",
         rec["code_env"]["verify_mean_s"] * 1e6,
         f"sandbox_n_{rec['code_env']['sandbox_verifications']}")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]))
    else:
        main()
