"""Disaggregated placement + threaded runtime system tests.

Promotes ``launch/disaggregated.py::demo`` into assertions: the device
pool splits into disjoint rollout/trainer submeshes, weights round-trip
trainer -> rollout exactly, and a decode step runs ON the rollout
submesh.  Adds the threaded-runtime equivalents: a multi-device smoke
with a hard deadline (a deadlock fails fast, not hangs) and a
threaded-vs-virtual semantic equivalence run on one device.

Multi-device tests spawn subprocesses with forced host device counts so
the main pytest process keeps a single device (same pattern as
tests/test_sharding.py).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.slow
def test_weights_round_trip_and_decode_on_rollout_submesh():
    """demo(), promoted: split 8 devices 50/50, init params on the
    trainer submesh, push to the rollout submesh, decode there."""
    out = _run("""
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.configs import get_model_config, reduced
        from repro.launch.disaggregated import push_weights, split_devices
        from repro.models.model import build_model

        roll_mesh, train_mesh = split_devices(0.5)
        roll_devs = set(roll_mesh.devices.flat)
        train_devs = set(train_mesh.devices.flat)
        assert roll_devs and train_devs and not (roll_devs & train_devs)

        cfg = reduced(get_model_config("areal-qwen-1.5b"))
        model = build_model(cfg, remat=False)
        with jax.set_mesh(train_mesh):
            params = model.init(jax.random.key(0))
        roll_params = push_weights(params, roll_mesh)

        # round-trip: the pushed tree is numerically identical
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(roll_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and lives on the rollout submesh, not the trainer's
        leaf = jax.tree.leaves(roll_params)[0]
        assert set(leaf.sharding.device_set) <= roll_devs

        with jax.set_mesh(roll_mesh):
            cache = model.init_cache(4, 32)
            toks = jnp.zeros((4, 8), jnp.int32)
            logits, cache = model.prefill(params=roll_params, tokens=toks,
                                          cache=cache)
            logits, cache = model.decode_step(
                roll_params, jnp.argmax(logits, -1).astype(jnp.int32), cache)
        assert set(logits.sharding.device_set) <= roll_devs
        print(json.dumps({"ok": True,
                          "rollout": len(roll_devs),
                          "trainer": len(train_devs),
                          "finite": bool(jnp.isfinite(logits).all())}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ok"] and res["finite"]
    assert res["rollout"] == 4 and res["trainer"] == 4


@pytest.mark.slow
def test_engine_placed_on_rollout_submesh_decodes_like_one_device():
    """RolloutEngine.place: params and KV cache committed to a 3-device
    rollout submesh, every step run whole on each device (shard_map, as
    Pallas kernels require on TPU), and greedy decode equal to an
    unplaced one-device engine on the same params."""
    out = _run("""
        import json
        import jax
        import numpy as np
        from repro.configs import get_model_config, reduced
        from repro.core import RolloutEngine
        from repro.core.config import EngineConfig
        from repro.data import tokenizer
        from repro.launch.disaggregated import split_devices
        from repro.models.model import build_model
        from repro.serve import Gateway

        roll_mesh, _ = split_devices(0.25)
        cfg = reduced(get_model_config("areal-qwen-1.5b"))
        model = build_model(cfg, remat=False)
        params = model.init(jax.random.key(0))
        prompts = [tokenizer.encode(f"{a}+{b}=", bos=True)
                   for a, b in ((1, 2), (3, 4), (5, 6))]

        def decode(mesh):
            eng = RolloutEngine(model, params, cfg=EngineConfig(
                n_slots=4, prompt_len=16, max_gen_len=12, temperature=0.0,
                cache="paged", prefill_chunk=8))
            if mesh is not None:
                eng.place(mesh)
            gw = Gateway(eng)
            rids = [gw.submit(p) for p in prompts]
            gw.run_until_idle()
            ends = [gw.drain(r)["end"] for r in rids]
            devs = sorted({d.id for x in jax.tree.leaves((eng.params, eng.cache))
                           for d in x.devices()})
            return ends, devs

        sub, sub_devs = decode(roll_mesh)
        one, _ = decode(None)
        print(json.dumps({
            "roll": sorted(d.id for d in roll_mesh.devices.flat),
            "placed": sub_devs,
            "same_tokens": [a["tokens"] == b["tokens"] for a, b in zip(sub, one)],
            "max_dlp": max(float(np.max(np.abs(np.subtract(
                a["logprobs"], b["logprobs"])), initial=0.0))
                for a, b in zip(sub, one))}))
    """, devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["roll"] == [0, 1, 2] and res["placed"] == res["roll"]
    assert all(res["same_tokens"])
    assert res["max_dlp"] < 1e-5


@pytest.mark.slow
def test_threaded_runtime_multi_device_smoke_bounded():
    """2-step threaded run on 4 fake devices through the real launcher.
    Both the in-runtime deadline (--run-timeout) and the subprocess
    timeout are hard bounds: a scheduling deadlock FAILS, it cannot hang
    the lane."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--runtime", "threaded",
         "--steps", "2", "--batch-size", "8", "--answers-per-prompt", "2",
         "--eta", "4", "--no-final-eval", "--run-timeout", "300"],
        capture_output=True, text=True, env=env, timeout=420)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["runtime"] == "threaded"
    assert res["steps"] == 2
    assert res["n_devices"] == 4
    assert res["trainer_busy_fraction"] > 0
    assert res["effective_throughput_tok_s"] > 0


def test_threaded_matches_virtual_semantics():
    """Same seed, same policy, different transport: the threaded runtime
    must enforce the staleness bound, consume every trajectory exactly
    once, and land within reward tolerance of the virtual executor.
    (Trajectory-level equality is NOT expected — thread interleaving is
    real nondeterminism; the POLICY invariants are what must hold.)"""
    import jax

    from repro.configs.base import ModelConfig, RLConfig
    from repro.core import (AsyncRLController, AsyncScheduler, EngineConfig,
                            PPOTrainer, RolloutEngine, ThreadedRuntime,
                            TimingModel)
    from repro.data import tokenizer
    from repro.data.dataset import PromptStream
    from repro.models.model import build_model

    CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=48,
                      n_heads=4, n_kv_heads=2, d_ff=96,
                      vocab_size=tokenizer.VOCAB_SIZE)
    ETA, STEPS, BATCH = 2, 3, 8

    def parts(seed=5):
        rl = RLConfig(batch_size=BATCH, answers_per_prompt=2,
                      max_staleness=ETA, interruptible=True,
                      ppo_minibatches=2, microbatch_token_budget=128,
                      lr=1e-3, max_prompt_len=16, max_gen_len=8)
        model = build_model(CFG, remat=False)
        params = model.init(jax.random.key(seed))
        engine = RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=4, prompt_len=16, max_gen_len=8, seed=seed))
        trainer = PPOTrainer(model, rl, params)
        sched = AsyncScheduler(
            prompt_stream=PromptStream(seed=seed, answers_per_prompt=2,
                                       max_operand=9), rl=rl)
        return engine, trainer, sched, rl

    eng_v, tr_v, sched_v, rl_v = parts()
    virtual = AsyncRLController(
        engine=eng_v, trainer=tr_v, scheduler=sched_v, rl=rl_v,
        timing=TimingModel(decode_step=lambda n: 0.01,
                           prefill=lambda t: 1e-4 * t,
                           train_step=lambda t: 0.2, weight_sync=0.01))
    hist_v = virtual.run(STEPS)

    eng_t, tr_t, sched_t, rl_t = parts()
    threaded = ThreadedRuntime(engine=eng_t, trainer=tr_t, scheduler=sched_t)
    hist_t = threaded.run(STEPS, timeout=300)

    # the virtual loop may have pre-popped the NEXT batch into its
    # in-flight train slot when the run target was reached
    inflight_v = len(virtual._train_batch or [])
    for name, ctl, hist, inflight in (("virtual", virtual, hist_v, inflight_v),
                                      ("threaded", threaded, hist_t, 0)):
        assert [h.version for h in hist] == list(range(1, STEPS + 1)), name
        # Eq. 3 bounds SUBMISSION staleness; small consumption-side slack
        assert max(h.staleness_max for h in hist) <= ETA + 2, name
        # use-once: exactly one consumption per trained trajectory
        assert ctl.buffer.total_consumed == STEPS * BATCH + inflight, name
        assert ctl.buffer.total_added >= ctl.buffer.total_consumed, name
        assert ctl.buffer.total_added - ctl.buffer.total_consumed == \
            len(ctl.buffer), name
    # weights propagated end-to-end in both transports
    assert eng_v.version == STEPS and eng_t.version == STEPS
    # same task, same seed: final rewards agree within sampling tolerance
    # (batches differ by interleaving, so this is a band, not equality)
    last_v = sum(h.reward_mean for h in hist_v[-2:]) / 2
    last_t = sum(h.reward_mean for h in hist_t[-2:]) / 2
    assert abs(last_v - last_t) <= 2.5, (last_v, last_t)


def test_virtual_executor_real_model_golden_history():
    import jax

    from repro.configs.base import ModelConfig, RLConfig
    from repro.core import (AsyncRLController, EngineConfig, PPOTrainer,
                            RolloutEngine, TimingModel)
    from repro.data import tokenizer
    from repro.data.dataset import PromptStream
    from repro.models.model import build_model

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=48,
                      n_heads=4, n_kv_heads=2, d_ff=96,
                      vocab_size=tokenizer.VOCAB_SIZE)
    rl = RLConfig(batch_size=8, answers_per_prompt=2, max_staleness=2,
                  decoupled_objective=True, interruptible=True,
                  ppo_minibatches=2, microbatch_token_budget=128, lr=1e-3,
                  max_prompt_len=16, max_gen_len=8)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(5))
    ctl = AsyncRLController(
        engine=RolloutEngine(model, params, cfg=EngineConfig(
            n_slots=4, prompt_len=16, max_gen_len=8, seed=5)),
        trainer=PPOTrainer(model, rl, params),
        prompt_stream=PromptStream(seed=5, answers_per_prompt=2,
                                   max_operand=9),
        rl=rl, timing=TimingModel(decode_step=lambda n: 0.01,
                                  prefill=lambda t: 1e-4 * t,
                                  train_step=lambda t: 0.2,
                                  weight_sync=0.01))
    hist = ctl.run(3)
    # The scheduler's guarantees, not sampled-token counts: those come
    # from random weights and move with every rounding change in the
    # installed JAX, while these hold on any numerics.
    assert [h.version for h in hist] == [1, 2, 3]
    assert all(a.clock < b.clock for a, b in zip(hist, hist[1:]))
    for h in hist:
        assert 0 <= h.staleness_mean <= h.staleness_max <= rl.max_staleness
        assert h.n_tokens > 0
        assert np.isfinite(h.loss) and np.isfinite(h.reward_mean)
    assert all(a.gen_tokens_total < b.gen_tokens_total
               and a.interruptions <= b.interruptions
               for a, b in zip(hist, hist[1:]))
    # use-once consumption: every trained trajectory (and the batch the
    # next, unfinished step already took) left the buffer exactly once,
    # and nothing admitted to it was lost
    buf = ctl.sched.buffer
    in_training = len(ctl._train_batch or [])
    assert buf.total_consumed == 3 * rl.batch_size + in_training
    assert buf.total_added == buf.total_consumed + len(buf)
