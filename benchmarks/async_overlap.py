"""Real wall-clock generation/training overlap of the threaded runtime.

The virtual-clock figures (fig1/table1/fig4) PROVE the async scheduling
policy; this benchmark measures the async *transport*: the threaded
disaggregated runtime (DESIGN.md §Async runtime) against a forced-serial
baseline that drives the SAME engine/trainer/scheduler on one thread in
strict generate-then-train alternation (the colocated-synchronous
regime).

Both runs execute in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — a fake
multi-device host — so the threaded runtime exercises the real
disaggregated submesh split (3 rollout / 1 trainer device) and weight
publication path.  Per mode we record, over a timed window that excludes
first-compile:

  * wall seconds and PPO versions completed,
  * effective throughput (tokens consumed by PPO updates / wall s),
  * trainer-busy fraction (wall time inside ``train_step``),
  * tokens generated *during* PPO updates — nonzero iff generation and
    training truly overlap (structurally zero for the serial baseline).

Results land in ``BENCH_async_overlap.json``; the paper-facing number is
the threaded / serial effective-throughput ratio (>=1.5x here, the same
direction as Table 1 at cluster scale).

Why a fixed 5-version window: the asynchrony advantage has two parts —
true wall-clock overlap, plus the eta-bounded *generate-ahead inventory*
(the rollout thread fills the staleness budget while the trainer is
busy, so the trainer never waits for data; the forced-serial baseline
cannot generate ahead by construction).  Both are the paper's mechanism
(Fig. 3).  The inventory part is bounded by eta * batch trajectories, so
on this container's 2 shared cores — where simultaneous decode and train
contend for the same silicon — very long windows converge toward the
contention-limited overlap-only ratio.  A short window right after
warmup measures the regime the paper actually runs in: trainer-bound
consumption against a standing staleness-window inventory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import bench_path, emit

DEVICES = 4
STEPS = 5               # measured versions; fixed window (see module doc)
# 2 warm-up versions, not 1: the first weight pickup with ACTIVE slots
# compiles the full-width re-prefill signature (~1s on CPU); one version
# can complete before any slot is mid-flight at pickup, leaking that
# compile into the timed window for exactly one of the two modes.
WARMUP_STEPS = 2


def _build(seed: int = 0):
    """A tiny balanced pipeline: generation and training each take a
    comparable share, so overlap is visible in the throughput ratio."""
    import jax

    from repro.configs.base import ModelConfig, RLConfig
    from repro.core import (AsyncScheduler, EngineConfig, PPOTrainer,
                            RolloutEngine, ThreadedRuntime)
    from repro.data import tokenizer
    from repro.data.dataset import PromptStream
    from repro.launch.train import _place_disaggregated
    from repro.models.model import build_model

    cfg = ModelConfig(name="bench-overlap", family="dense", n_layers=2,
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
    sched = AsyncScheduler(
        prompt_stream=PromptStream(seed=seed, answers_per_prompt=4,
                                   max_operand=9), rl=rl)
    roll_mesh = None
    n_roll = n_train = 1
    if len(jax.devices()) > 1:
        roll_mesh, train_mesh = _place_disaggregated(engine, trainer, 0.25)
        n_roll = roll_mesh.devices.size
        n_train = train_mesh.devices.size
    rt = ThreadedRuntime(engine=engine, trainer=trainer, scheduler=sched,
                         rollout_mesh=roll_mesh)
    return rt, n_roll, n_train


def _measure(mode: str, steps: int, seed: int = 0):
    import time

    rt, n_roll, n_train = _build(seed)
    if mode == "serial":
        drive = rt.run_serial
    else:
        def drive(n):
            return rt.run(n, timeout=600)   # a deadlock fails, not hangs
    drive(WARMUP_STEPS)                       # first-compiles outside the window
    v0 = rt.trainer.version
    busy0, tok_during0 = rt.trainer_busy_s, rt.tokens_during_train
    gen0, hist0 = rt.engine.tokens_generated, len(rt.history)
    t0 = time.perf_counter()
    drive(steps)
    wall = time.perf_counter() - t0
    consumed = sum(h.n_tokens for h in rt.history[hist0:])
    return {
        "mode": mode,
        "versions": rt.trainer.version - v0,
        "wall_s": round(wall, 3),
        "tokens_consumed": consumed,
        "effective_throughput_tok_s": round(consumed / wall, 2),
        "trainer_busy_fraction": round((rt.trainer_busy_s - busy0) / wall, 4),
        "tokens_generated": rt.engine.tokens_generated - gen0,
        "tokens_during_train": rt.tokens_during_train - tok_during0,
        "rollout_devices": n_roll, "trainer_devices": n_train,
    }


def _traced(trace_path: str) -> None:
    """Short traced re-run, SEPARATE from the timed windows above (the
    cost of tracing has its own benchmark, trace_overhead): writes the
    Chrome/Perfetto timeline that ``tools/trace_check.py`` validates
    and docs/OPERATIONS.md's walkthrough opens (DESIGN.md §Telemetry)."""
    from repro.obs import export, trace as tracing

    tracing.configure(enabled=True, actor="async_overlap")
    rt, _, _ = _build(seed=1)
    rt.run(WARMUP_STEPS + 1, timeout=600)
    tracing.configure(enabled=False)
    export.write_trace(trace_path)


def _child(steps: int, trace_path: str) -> None:
    import jax

    out = {"devices": len(jax.devices()), "steps": steps,
           "threaded": _measure("threaded", steps),
           "serial": _measure("serial", steps)}
    _traced(trace_path)
    print("BENCH_JSON=" + json.dumps(out), flush=True)


def main() -> None:
    steps = STEPS                             # >=5 PPO versions, smoke or full
    env = dict(os.environ)
    # a CPU structure check on fake host devices: never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICES}"
    # resolved in the parent: the child may not see run.py's SMOKE flag
    trace_path = os.path.abspath(bench_path("BENCH_async_overlap_trace.json"))
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.async_overlap",
         "--child", str(steps), trace_path],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("BENCH_JSON=")][-1]
    rec = json.loads(line[len("BENCH_JSON="):])

    thr = rec["threaded"]["effective_throughput_tok_s"]
    ser = rec["serial"]["effective_throughput_tok_s"]
    rec["throughput_ratio"] = round(thr / ser, 3) if ser else None
    rec["overlap_demonstrated"] = (
        rec["threaded"]["trainer_busy_fraction"] > 0
        and rec["threaded"]["tokens_during_train"] > 0)
    # gate the traced re-run: well-formed timeline with at least one
    # wall-clock-concurrent rollout/trainer span pair (the overlap the
    # timed ratio above measures, now visible in the artifact)
    from tools import trace_check
    tr = trace_check.load(trace_path)
    errors = trace_check.validate(tr)
    rec["trace"] = {
        "valid": not errors,
        "events": len(tr.get("traceEvents", [])),
        "concurrent_span_pairs": trace_check.concurrent_span_pairs(
            tr, "rollout", "trainer"),
        "errors": errors[:5],
    }
    with open(bench_path("BENCH_async_overlap.json"), "w") as f:
        json.dump(rec, f, indent=2)

    us_per_version = rec["threaded"]["wall_s"] / rec["threaded"]["versions"] * 1e6
    emit("async_overlap_threaded", us_per_version,
         f"throughput_x{rec['throughput_ratio']:.2f}")
    emit("async_overlap_busy_frac",
         rec["threaded"]["trainer_busy_fraction"] * 1e6,
         f"tok_during_train_{rec['threaded']['tokens_during_train']}")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]), sys.argv[3])
    else:
        main()
