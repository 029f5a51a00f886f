"""Distributed AReaL training launcher.

Runs the full asynchronous RL pipeline (rollout engine + PPO trainer +
the shared scheduling core) for a selected architecture at a selected
scale and under a selected executor:

  * ``--scale laptop``  (default): reduced model on the local devices —
    the runnable end-to-end driver (examples/ wrap this).
  * ``--scale pod``: the published config (``launch/cli.py::
    model_config``).  Its training state must fit the trainer's devices:
    areal-qwen-1.5b at full depth needs ~16 bytes per parameter (f32
    params, Adam m/v, grads), 24.6 GB, more than one 16 GB v5e chip.  A
    caller that cuts the depth passes a ``ModelConfig`` to
    ``run_training`` instead of an arch id (``chip_smoke.py`` does).

  * ``--runtime virtual`` (default): the deterministic virtual-clock
    executor (core/controller.py) — real computation, simulated
    concurrency under an analytic TimingModel.
  * ``--runtime threaded``: the real threaded disaggregated runtime
    (core/runtime.py, DESIGN.md §Async runtime): a rollout thread and a
    trainer thread on disjoint device submeshes.  When more than one
    device is visible the pool is split by
    ``launch/disaggregated.py::split_devices`` (paper Sec 7.1's 75/25
    inference/training layout by default) and weights flow
    trainer→rollout through the ParameterStore + ``push_weights``; on a
    single device both threads share it (concurrency without
    disaggregation).  Run with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for a local
    multi-device pool.
  * ``--runtime fleet``: the multi-process elastic fleet
    (core/fleet.py, DESIGN.md §Fleet runtime): ``--rollout-workers N``
    rollout processes and ``--trainer-procs M`` trainer replicas under
    a supervising parent, with heartbeats, crash requeue/respawn and
    (``--elastic``) reward-backlog-driven grow/shrink.  Engines run
    with per-request RNG so trajectories are reproducible across any
    worker placement.

On a cluster, each pod runs this entry point under its own process
group.  Every flag is documented in docs/OPERATIONS.md.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Union

import jax

from repro.configs.base import ModelConfig, RLConfig
from repro.core.config import EngineConfig
from repro.core import (AsyncRLController, AsyncScheduler, PPOTrainer,
                        ParameterStore, RolloutEngine, ThreadedRuntime)
from repro.core.simulator import HardwareModel, WorkloadModel, make_llm_timing
from repro.data.dataset import PromptStream
from repro.launch import cli, disaggregated
from repro.models.model import build_model
from repro.obs import metrics as obs_metrics
from repro.obs import trace


def _place_disaggregated(engine, trainer, train_fraction: float):
    """Split the visible device pool into rollout/trainer submeshes and
    commit each role's state to its own submesh (computation follows
    committed data, so the two threads run on disjoint devices)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    roll_mesh, train_mesh = disaggregated.split_devices(train_fraction)
    engine.place(roll_mesh)
    train_sharding = NamedSharding(train_mesh, P())
    trainer.params = jax.device_put(trainer.params, train_sharding)
    trainer.opt_state = jax.device_put(trainer.opt_state, train_sharding)
    return roll_mesh, train_mesh


def _make_env(env: str, *, seed: int, max_operand: int, sandbox_timeout: float):
    from repro.env import make_env
    kwargs = {"seed": seed}
    if env == "code":
        kwargs["timeout_s"] = sandbox_timeout
    else:                                  # math / multiturn
        kwargs["max_operand"] = max_operand
    return make_env(env, **kwargs)


def run_training(arch: Union[str, ModelConfig] = "areal-qwen-1.5b", *,
                 steps: int = 25,
                 scale: str = "laptop", eta: int = 4, decoupled: bool = True,
                 interruptible: bool = True, batch_size: int = 32,
                 answers_per_prompt: int = 4, n_slots: int = 16,
                 prompt_len: int = 24, max_gen_len: int = 16,
                 lr: float = 3e-4, seed: int = 1, adv_estimator: str = "grpo",
                 ckpt_dir: str = "", log_every: int = 1, max_operand: int = 9,
                 colocated_sync: bool = False, on_step=None,
                 runtime: str = "virtual", train_fraction: float = 0.25,
                 run_timeout: float = 0.0, final_eval: bool = True,
                 prefill_chunk: int = 0, env: str = "",
                 reward_workers: int = 0, reward_latency: float = 0.0,
                 reward_backlog: int = 64, sandbox_timeout: float = 2.0,
                 rollout_workers: int = 2, trainer_procs: int = 1,
                 elastic: bool = False, min_workers: int = 1,
                 weight_stream: str = "full", fused_decode: str = "",
                 spec_decode: int = 0, spec_draft_units: int = 0,
                 cache: str = "ring", block_size: int = 16,
                 pool_blocks: int = 0, evict: str = "off"):
    """End-to-end AReaL training on a verifiable environment.

    ``env`` selects the workload (DESIGN.md §Environments and reward
    service): "" = the legacy synchronous math path (bit-for-bit the
    pre-env behavior), "math"/"code"/"multiturn" route scoring through
    the Environment protocol.  ``reward_workers > 0`` (threaded runtime)
    scores on an ``AsyncRewardService`` pool off the rollout thread;
    with the virtual runtime, ``reward_latency`` models the pipelined
    verification delay instead.  "multiturn" installs the engine
    continuation hook (requires chunked prefill; enabled automatically).

    ``arch`` is an architecture id, sized by ``scale``
    (``cli.model_config``), or a ``ModelConfig`` used as given — the way
    a caller runs a published config with its depth cut, say.

    Returns (executor, trainer, reward_service); the executor is the
    virtual-clock controller, the threaded runtime or the process fleet
    depending on ``runtime`` — all expose
    .history/.clock/.effective_throughput()."""
    assert runtime in ("virtual", "threaded", "fleet"), runtime
    assert env in ("", "math", "code", "multiturn"), env
    if isinstance(arch, ModelConfig):
        cfg = full_cfg = arch
    else:
        cfg = cli.model_config(arch, scale)
        full_cfg = cli.model_config(arch, "pod")
    rl = RLConfig(batch_size=batch_size, answers_per_prompt=answers_per_prompt,
                  max_staleness=eta, decoupled_objective=decoupled,
                  interruptible=interruptible, lr=lr,
                  microbatch_token_budget=max(256, prompt_len + max_gen_len),
                  ppo_minibatches=2, total_steps=steps,
                  adv_estimator=adv_estimator,
                  max_prompt_len=prompt_len, max_gen_len=max_gen_len)

    if reward_workers > 0 and not env:
        env = "math"                       # async scoring needs an Environment
    environment = continuation = None
    if env:
        environment = _make_env(env, seed=seed, max_operand=max_operand,
                                sandbox_timeout=sandbox_timeout)
        continuation = environment.continuation_hook()
        if continuation is not None and prefill_chunk <= 0:
            # multi-turn continuation re-enters the FIFO ingest queue,
            # which only the chunked engine has
            prefill_chunk = prompt_len

    eng_extra = {"cache": cache, "block_size": block_size,
                 "n_blocks": pool_blocks or None, "evict": evict}
    if fused_decode:
        eng_extra["cache"] = "paged"       # the fused tail is a paged-path jit
        eng_extra["fused_decode"] = fused_decode
    if spec_decode:
        eng_extra["spec_decode"] = spec_decode
        eng_extra["spec_draft_units"] = spec_draft_units or None
        eng_extra["temperature"] = 0.0     # speculation is greedy-only

    model = build_model(cfg, remat=False)
    engine = trainer = None
    if runtime != "fleet":                 # fleet workers build their own
        params = model.init(jax.random.key(seed))
        engine = RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=n_slots, prompt_len=prompt_len, max_gen_len=max_gen_len,
            seed=seed, prefill_chunk=prefill_chunk,
            continuation=continuation, **eng_extra))
        trainer = PPOTrainer(model, rl, params)
        # hold no reference of our own: the initial tree is freed once the
        # engine and the trainer have moved past it (1.7 GB of a 16 GB
        # chip at areal-qwen-1.5b widths with 4 layers)
        del params
    store = ParameterStore(ckpt_dir=ckpt_dir or None,
                           ckpt_every=10 if ckpt_dir else 0)
    if environment is None:
        stream = PromptStream(seed=seed, answers_per_prompt=answers_per_prompt,
                              max_operand=max_operand)
    else:
        from repro.env import EnvPromptStream
        stream = EnvPromptStream(environment, answers_per_prompt)
    service = None
    if reward_workers > 0:
        if runtime not in ("threaded", "fleet"):
            raise ValueError(
                "--reward-workers needs --runtime threaded or fleet (the "
                "virtual executor models pipelined verification with "
                "reward_latency instead)")
        from repro.env import AsyncRewardService
        service = AsyncRewardService(environment, n_workers=reward_workers,
                                     max_backlog=reward_backlog)

    logs = []

    def _on_step(log):
        logs.append(log)
        if on_step:
            on_step(log)
        if runtime == "virtual":
            # the threaded runtime publishes on the trainer thread itself;
            # here publication is the virtual executor's side channel
            store.publish(log.version, trainer.params, {"clock": log.clock})
        if log.version % log_every == 0:
            print(f"v{log.version:4d} clock={log.clock:10.2f}s "
                  f"reward={log.reward_mean:+6.2f} acc={log.accuracy:.3f} "
                  f"stale={log.staleness_mean:.2f}/{log.staleness_max} "
                  f"loss={log.loss:+.4f} interrupts={log.interruptions}",
                  flush=True)

    sched = AsyncScheduler(prompt_stream=stream, rl=rl, on_step=_on_step,
                           env=environment, reward_service=service)

    if runtime == "threaded":
        roll_mesh = None
        if len(jax.devices()) > 1:
            roll_mesh, train_mesh = _place_disaggregated(engine, trainer,
                                                         train_fraction)
            print(f"disaggregated: {roll_mesh.devices.size} rollout / "
                  f"{train_mesh.devices.size} trainer devices", flush=True)
        ctl = ThreadedRuntime(engine=engine, trainer=trainer, scheduler=sched,
                              store=store, rollout_mesh=roll_mesh,
                              weight_stream=weight_stream)
        ctl.run(steps, timeout=run_timeout or None)
    elif runtime == "fleet":
        from repro.core import fleet as fleet_mod
        if continuation is not None:
            raise ValueError(
                "--runtime fleet does not support multi-turn environments "
                "(the continuation hook would have to live inside the "
                "rollout worker process)")
        ctl = fleet_mod.FleetRuntime(
            scheduler=sched,
            engine_factory=fleet_mod.build_engine,
            engine_factory_kwargs=dict(
                model_cfg=cfg, seed=seed,
                engine_kwargs=dict(n_slots=n_slots, prompt_len=prompt_len,
                                   max_gen_len=max_gen_len,
                                   prefill_chunk=prefill_chunk,
                                   rng="request", **eng_extra)),
            trainer_factory=fleet_mod.build_trainer,
            trainer_factory_kwargs=dict(model_cfg=cfg, rl=rl, seed=seed),
            n_slots=n_slots, rollout_workers=rollout_workers,
            trainer_procs=trainer_procs, store=store, elastic=elastic,
            min_workers=min_workers, weight_stream=weight_stream)
        try:
            ctl.run(steps, timeout=run_timeout or None)
        finally:
            ctl.close()
        trainer = ctl.trainer              # canonical post-run state view
    else:
        # virtual-clock cost model for a small pod (sec 7.1: 75/25 split);
        # costs reflect the TARGET architecture's size, not the reduced model
        hw = HardwareModel()
        wl = WorkloadModel(n_params=float(full_cfg.param_count()))
        timing = make_llm_timing(hw, wl,
                                 n_gen_devices=96 if not colocated_sync else 128,
                                 n_train_devices=32 if not colocated_sync else 128,
                                 colocated=colocated_sync)
        # pipelined verification latency under the virtual clock — the
        # sim-side mirror of the threaded runtime's reward workers
        timing.reward_latency = reward_latency
        ctl = AsyncRLController(engine=engine, trainer=trainer,
                                scheduler=sched, rl=rl, timing=timing)
        if trace.get().enabled:
            # the virtual executor traces in its own time base: spans
            # carry the simulated clock, not wall time (DESIGN.md
            # §Clock domains)
            trace.get().set_clock(lambda: ctl.clock)
        ctl.run(steps)
    if (scale == "laptop" and final_eval and env in ("", "math")
            and trainer.params is not None):
        # paper protocol: evaluate the FINAL checkpoint on held-out problems
        from repro.core.evaluate import evaluate
        res = evaluate(model, trainer.params, n_problems=64,
                       prompt_len=prompt_len, max_gen_len=max_gen_len,
                       max_operand=max_operand)
        ctl.final_eval = res
        print(f"final held-out eval: {res.accuracy:.1%} "
              f"({res.n_correct}/{res.n}, mean len {res.mean_len:.1f})")
    return ctl, trainer, ctl.reward


def main():
    ap = argparse.ArgumentParser()
    cli.add_model_flags(ap)
    ap.add_argument("--steps", type=int, default=25)
    # engine / env / runtime flags are declared once, in launch/cli.py
    cli.add_engine_flags(ap, slots=16, seed=1)
    cli.add_env_flags(ap, default="", allow_legacy=True)
    cli.add_runtime_flags(ap)
    cli.add_obs_flags(ap)
    ap.add_argument("--eta", type=int, default=4,
                    help="max staleness (-1 = unbounded, 0 = synchronous)")
    ap.add_argument("--naive-ppo", action="store_true",
                    help="disable the decoupled objective (Eq. 2 baseline)")
    ap.add_argument("--no-interrupt", action="store_true")
    ap.add_argument("--sync-colocated", action="store_true",
                    help="model the synchronous shared-device baseline")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--answers-per-prompt", type=int, default=4)
    ap.add_argument("--adv", default="grpo", choices=["grpo", "rloo", "mc"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--no-final-eval", action="store_true")
    args = ap.parse_args()
    cli.enable_compile_cache()
    cli.obs_setup(args, actor="train")

    t0 = time.time()
    ctl, trainer, reward = run_training(
        args.arch, steps=args.steps, scale=args.scale, eta=args.eta,
        decoupled=not args.naive_ppo, interruptible=not args.no_interrupt,
        batch_size=args.batch_size, answers_per_prompt=args.answers_per_prompt,
        n_slots=args.slots, prompt_len=args.prompt_len,
        max_gen_len=args.max_gen,
        adv_estimator=args.adv, seed=args.seed, ckpt_dir=args.ckpt_dir,
        colocated_sync=args.sync_colocated, runtime=args.runtime,
        train_fraction=args.train_fraction, run_timeout=args.run_timeout,
        final_eval=not args.no_final_eval, prefill_chunk=args.prefill_chunk,
        env=args.env, reward_workers=args.reward_workers,
        reward_latency=args.reward_latency,
        reward_backlog=args.reward_backlog,
        sandbox_timeout=args.sandbox_timeout,
        rollout_workers=args.rollout_workers,
        trainer_procs=args.trainer_procs, elastic=args.elastic,
        min_workers=args.min_workers, weight_stream=args.weight_stream,
        fused_decode=args.fused_decode, spec_decode=args.spec_decode,
        spec_draft_units=args.spec_draft_units,
        cache=args.cache, block_size=args.block_size,
        pool_blocks=args.pool_blocks, evict=args.evict)
    out = {
        "arch": args.arch, "runtime": args.runtime, "steps": trainer.version,
        "wall_s": round(time.time() - t0, 1),
        "final_accuracy": reward.recent_accuracy,
        "effective_throughput_tok_s": ctl.effective_throughput(),
        "staleness_hist": ctl.stal_stats.histogram(),
    }
    if args.env:
        out["env"] = args.env
        eng_stats = getattr(ctl, "engine", None)
        if eng_stats is not None and hasattr(eng_stats, "stats"):
            s = eng_stats.stats()
            out["continuations"] = s.get("continuations", 0)
    if args.fused_decode or args.spec_decode:
        eng = getattr(ctl, "engine", None)
        if eng is not None:
            out["decode_dispatches"] = eng.decode_dispatches
            if args.spec_decode:
                out["accepted_tokens_per_step"] = round(
                    eng.accepted_tokens_per_step, 3)
                out["draft_acceptance_rate"] = round(
                    eng.draft_acceptance_rate, 3)
    svc = getattr(ctl, "reward_service", None)
    if svc is not None:
        out["reward_service"] = svc.stats()
        svc.close()
    if args.runtime == "virtual":
        out["virtual_hours"] = ctl.clock / 3600
    else:
        out["run_wall_s"] = round(ctl.clock, 3)
        out["trainer_busy_fraction"] = round(
            ctl.trainer_busy_s / max(ctl.clock, 1e-9), 4)
        out["tokens_during_train"] = ctl.tokens_during_train
        out["n_devices"] = len(jax.devices())
    if args.runtime == "fleet":
        out["respawns"] = ctl.respawns
        out["requeued"] = ctl.requeued
        out["fleet_events"] = len(ctl.registry.events)
    snap_stats = {"scheduler": obs_metrics.scrape(
        ctl.sched, surfaces=("publication_stats",))}
    eng = getattr(ctl, "engine", None)
    if eng is not None and hasattr(eng, "stats"):
        snap_stats["engine"] = obs_metrics.scrape(
            eng, surfaces=("stats", "stream_stats"))
    if reward is not None and hasattr(reward, "stats"):
        snap_stats["reward"] = reward.stats()
    out.update(cli.obs_finish(args, stats=snap_stats))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
