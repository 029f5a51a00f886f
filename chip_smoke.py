"""Run the system's main path once on TPU and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one four-chip host: the split layout

One chip, in one process and in this order:

1. kernels: the Pallas kernels compiled for the chip at areal-qwen-1.5b
   widths, in bf16 and f32, against the jnp oracles of
   ``repro/kernels/ref.py`` on the same inputs.
2. serve: the gateway path of ``repro.launch.serve`` (``build_gateway`` +
   ``run_offline``) at ``--scale pod`` (28 layers, vocab 151,936): 8
   greedy requests over 4 sessions, then the first two again with the
   jnp kernels, compared token by token.
3. rl: ``run_training(runtime="threaded")`` at areal-qwen-1.5b widths with
   the depth cut to 4 layers (full-depth training state does not fit one
   16 GB chip): 3 PPO versions, with weight publication interrupting
   decodes in flight, then one train step on answers scored apart, whose
   gradient must be finite and non-zero.

``--four-chips`` runs only the disaggregated layout: the RL loop of
phase 3 split 3 rollout : 1 trainer chip, a ``push_weights`` round trip
checked bit for bit on every rollout chip, and greedy decode on the
rollout submesh against a one-chip decode of the same params.  It
prints where the engine's and the trainer's arrays live.

Each phase prints one JSON line: its checks, wall and compile seconds,
and the process's peak device memory so far.  The last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits non-zero and prints no result.  Weights
are random, made from fixed seeds.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "areal-qwen-1.5b"
# Kernel phase: max |pallas - oracle| / (1 + |oracle|) per element.  bf16
# inputs and outputs carry 8 mantissa bits (relative 2^-8 per element,
# compounded through the softmax sums); f32 leaves room for the MXU's
# passes.  The oracle runs on the same inputs upcast to f32 at "highest"
# precision.
KERNEL_TOL = {"bfloat16": 5e-2, "float32": 1e-2}
# Serve phase: per-token |logprob difference| between the Pallas and the
# jnp run, over the tokens both chose greedily.  Only attention differs
# between the two programs (summation order, and the default one-pass
# bf16 matmul the jnp oracle gets for f32 on TPU), through 28 layers.
LOGPROB_TOL = 0.05
SERVE_FLAGS = ["--scale", "pod", "--cache", "paged", "--prefill-chunk", "64",
               "--fused-decode", "fused", "--requests", "8", "--sessions",
               "4", "--prompt-len", "64", "--max-gen", "64", "--slots", "8"]
COMPARE = 2               # requests rerun with the jnp kernels
RL_LAYERS = 4             # the one cut: depth, so the trainer fits one chip
# Fewer slots than a batch: each version's rollout takes two rounds of
# decoding, longer than a train step, so a publication lands mid-decode.
RL = dict(runtime="threaded", env="math", cache="paged", prefill_chunk=16,
          eta=4, steps=3, batch_size=16, answers_per_prompt=4, n_slots=8,
          prompt_len=24, max_gen_len=256, seed=1, final_eval=False,
          run_timeout=900.0)

_compile_s = [0.0]


def _count_compile(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


class Phase:
    """Times a phase and prints its JSON line on exit, pass or fail;
    an exception is never swallowed."""

    def __init__(self, name):
        self.name, self.out = name, {}

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        return self

    def check(self, cond, what):
        if not cond:
            raise AssertionError(f"{self.name}: {what}")

    def __exit__(self, exc_type, exc, tb):
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        line = {"phase": self.name, "ok": exc_type is None,
                "wall_s": time.perf_counter() - self.t0,
                "compile_s": _compile_s[0] - self.c0,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_in_use": stats.get("bytes_in_use"),
                **self.out}
        if exc is not None:
            line["error"] = f"{exc_type.__name__}: {exc}"[:2000]
        print(json.dumps(line, default=str), flush=True)
        return False


def _free():
    gc.collect()
    import jax
    jax.clear_caches()


# ---------------------------------------------------------------------------
# 1. kernels
# ---------------------------------------------------------------------------

def _kernel_inputs(seed=0):
    """areal-qwen-1.5b attention widths; a 16-slot x 1024-token paged pool
    with random block tables, partial last blocks and one empty slot."""
    import numpy as np

    from repro.configs import get_model_config
    from repro.kernels import ops

    cfg = get_model_config(ARCH)
    h, hkv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    slots, bs, max_len, chunk = 16, 16, 1024, 64
    e = max_len // bs
    n = slots * e
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s, dtype=np.float32)  # noqa: E731
    tables = np.full((slots, e), -1, np.int32)
    t = np.zeros(slots, np.int32)
    perm = rng.permutation(n)
    for i in range(slots - 1):                   # the last slot stays empty
        nb = int(rng.integers(1, e + 1))
        tables[i, :nb] = perm[i * e:i * e + nb]
        t[i] = rng.integers((nb - 1) * bs, nb * bs)
    q_pos = t[:2, None] - np.arange(chunk)[::-1][None, :]
    q_pos = np.where(q_pos >= 0, q_pos, -1).astype(np.int32)
    seg = np.sort(rng.integers(0, 3, (2, max_len)), axis=1).astype(np.int32)
    ring_pos = np.tile(np.arange(max_len, dtype=np.int32), (slots, 1))
    ring_pos[rng.random((slots, max_len)) < 0.2] = -1
    kp, vp = f(n, bs, hkv, hd), f(n, bs, hkv, hd)
    lru = get_model_config("recurrentgemma-9b").lru_width
    # Rows with no key to attend (an empty slot, a padded query) have no
    # specified output: the engine never reads them, so neither does the
    # comparison.  Each entry: (kernel, args, rows compared).
    slot_live = tables.max(axis=1) >= 0
    ring_live = ((ring_pos >= 0) & (ring_pos <= t[:, None])).any(axis=1)
    every = np.ones(2, bool)
    return {
        "paged_decode_attention": (ops.paged_decode_attention,
                                   (f(slots, h, hd), kp, vp, tables, t),
                                   slot_live),
        "paged_prefill_attention": (ops.paged_prefill_attention,
                                    (f(2, chunk, h, hd), kp, vp, tables[:2],
                                     q_pos), q_pos >= 0),
        "fused_decode_tail": (ops.fused_decode_tail,
                              (f(slots, h, hd), kp, vp,
                               f(h * hd, d) * np.float32((h * hd) ** -0.5), tables, t),
                              slot_live),
        "flash_attention": (ops.flash_attention,
                            (f(2, max_len, h, hd), f(2, max_len, hkv, hd),
                             f(2, max_len, hkv, hd), seg), every),
        "decode_attention": (ops.decode_attention,
                             (f(slots, h, hd), f(slots, max_len, hkv, hd),
                              f(slots, max_len, hkv, hd), ring_pos, t),
                             ring_live),
        "linear_scan": (ops.linear_scan,
                        (rng.uniform(0.7, 1.0, (2, max_len, lru))
                         .astype(np.float32), f(2, max_len, lru), f(2, lru)),
                        every),
    }


def kernel_phase():
    import jax
    import jax.numpy as jnp
    import numpy as np

    with Phase("kernels") as ph:
        errs, rel, compile_s = {}, {}, {}
        for dtype in (jnp.bfloat16, jnp.float32):
            dname = jnp.dtype(dtype).name
            for name, (fn, host, live) in _kernel_inputs().items():
                args = [jnp.asarray(a, dtype if a.dtype == np.float32 else a.dtype)
                        for a in host]
                t0 = time.perf_counter()
                compiled = jax.jit(functools.partial(fn, backend="pallas")) \
                    .lower(*args).compile()
                compile_s[f"{name}/{dname}"] = time.perf_counter() - t0
                ph.check("tpu_custom_call" in compiled.as_text(),
                         f"{name}/{dname}: no Pallas kernel in the program")
                got = jax.tree.leaves(compiled(*args))
                with jax.default_matmul_precision("highest"):
                    want = jax.tree.leaves(jax.jit(functools.partial(
                        fn, backend="jnp"))(*[a.astype(jnp.float32)
                                               if a.dtype == dtype else a
                                               for a in args]))
                got = [np.asarray(g, np.float32)[live] for g in got]
                want = [np.asarray(w)[live] for w in want]
                key = f"{name}/{dname}"
                errs[key] = max(float(np.abs(g - w).max())
                                for g, w in zip(got, want))
                rel[key] = max(float((np.abs(g - w) / (1 + np.abs(w))).max())
                               for g, w in zip(got, want))
        ph.out.update(max_abs_err=errs, max_err_over_1_plus_ref=rel,
                      tolerance=KERNEL_TOL, kernel_compile_s=compile_s)
        bad = {k: v for k, v in rel.items()
               if not v <= KERNEL_TOL[k.split("/")[1]]}
        ph.check(not bad, f"errors above tolerance: {bad}")
    _free()


# ---------------------------------------------------------------------------
# 2. serve
# ---------------------------------------------------------------------------

def _serve(flags):
    from repro.launch import serve

    args = serve.build_parser().parse_args(flags)
    gw, env = serve.build_gateway(args, temperature=0.0)
    cfg = gw.engine.model.cfg
    results = {}
    summary = serve.run_offline(gw, env, args, results)
    return cfg, summary, [results[r] for r in sorted(results)]


def serve_phase(flags=SERVE_FLAGS, compare=COMPARE):
    import numpy as np

    from repro.kernels import ops

    with Phase("serve") as ph:
        backend = ops.current_backend()
        cfg, summary, runs = _serve(flags)
        _free()
        with ops.use_backend("jnp"):
            _, _, ref_runs = _serve(flags + ["--requests", str(compare)])
        _free()
        ph.out.update(backend=backend, n_layers=cfg.n_layers,
                      vocab=cfg.vocab_size, requests=len(runs),
                      generated_tokens=summary["generated_tokens"],
                      prefix_hit_rate=summary["prefix_hit_rate"])
        for r in runs:
            lp = np.asarray(r["logprobs"], np.float64)
            ph.check(len(r["tokens"]) > 0 and len(lp) == len(r["tokens"])
                     and np.isfinite(lp).all(),
                     f"request {r['rid']}: {len(r['tokens'])} tokens, "
                     f"logprobs finite={np.isfinite(lp).all()}")
        cmp = []
        for a, b in zip(runs[:compare], ref_runs):
            ph.check(a["prompt"] == b["prompt"], "jnp rerun got other prompts")
            n = min(len(a["tokens"]), len(b["tokens"]))
            same = next((i for i in range(n)
                         if a["tokens"][i] != b["tokens"][i]), n)
            dlp = np.abs(np.subtract(a["logprobs"][:same],
                                     b["logprobs"][:same]))
            cmp.append({"rid": a["rid"], "tokens": n,
                        "first_differing_step": same if same < n else None,
                        "max_abs_dlogprob": float(dlp.max(initial=0.0))})
            ph.check(float(dlp.max(initial=0.0)) <= LOGPROB_TOL,
                     f"pallas vs jnp logprobs differ by {dlp.max()}")
        ph.out.update(pallas_vs_jnp=cmp, logprob_tol=LOGPROB_TOL)
        ph.check(backend == "pallas", f"serving traced {backend} kernels")
        ph.check(cfg.n_layers == 28 and cfg.vocab_size == 151_936,
                 f"not the published config: {cfg.n_layers} layers, "
                 f"vocab {cfg.vocab_size}")


# ---------------------------------------------------------------------------
# 3. RL loop
# ---------------------------------------------------------------------------

def rl_config(n_layers=RL_LAYERS):
    """areal-qwen-1.5b at its published widths, depth cut to n_layers."""
    from repro.configs import get_model_config
    full = get_model_config(ARCH)
    return dataclasses.replace(full, n_layers=n_layers,
                               name=f"{full.name}-{n_layers}l")


def _rl_run(ph, cfg, **overrides):
    import numpy as np

    from repro.configs import get_model_config
    from repro.core.trainer import TRAIN_ATTENTION
    from repro.launch.train import run_training

    kw = {**RL, **overrides}
    ctl, trainer, _ = run_training(cfg, **kw)
    hist = ctl.history
    ph.out.update(
        cut={"n_layers": [get_model_config(ARCH).n_layers, cfg.n_layers]},
        trainer_attention=TRAIN_ATTENTION, version=trainer.version,
        loss=[h.loss for h in hist],
        grad_norm=[h.diag.get("grad_norm") for h in hist],
        interruptions=ctl.engine.interruptions,
        staleness_max=max((h.staleness_max for h in hist), default=None),
        gen_tokens=hist[-1].gen_tokens_total if hist else 0)
    ph.check(trainer.version == kw["steps"],
             f"trainer at version {trainer.version}")
    ph.check(all(np.isfinite(h.loss) and np.isfinite(h.diag["grad_norm"])
                 for h in hist), "non-finite loss or grad norm")
    ph.check(ctl.engine.interruptions > 0, "no decode was interrupted")
    ph.check(max(h.staleness_max for h in hist) <= kw["eta"],
             "staleness above eta")
    m = _scored_apart_step(trainer)
    ph.out["scored_apart_step"] = {"loss": m.loss,
                                   "grad_norm": m.diag["grad_norm"]}
    ph.check(np.isfinite(m.loss) and np.isfinite(m.diag["grad_norm"])
             and m.diag["grad_norm"] > 0, "no finite, non-zero gradient")
    return ctl, trainer


def _scored_apart_step(trainer):
    """One more train step, on answers scored apart.  With random weights
    the loop scores every answer alike, so its GRPO advantages, loss and
    gradient are all zero; this step's gradient is not."""
    import math

    from repro.core.buffer import Trajectory
    from repro.data import tokenizer

    lp = -math.log(trainer.model.cfg.vocab_size)   # a near-uniform policy
    batch = []
    for i, (a, b) in enumerate(((1, 2), (3, 4), (5, 6), (7, 8))):
        resp = tokenizer.encode(str(a + b + i % 2), eos=True)
        batch.append(Trajectory(
            rid=i, prompt_id=i // 2,
            prompt_tokens=tokenizer.encode(f"{a}+{b}=", bos=True),
            response_tokens=resp, behav_logprobs=[lp] * len(resp),
            versions=[trainer.version] * len(resp),
            behavior_version=trainer.version,
            reward=-5.0 if i % 2 else 5.0))
    return trainer.train_step(batch)


def rl_phase(cfg=None, **overrides):
    with Phase("rl") as ph:
        _rl_run(ph, cfg or rl_config(), **overrides)
    _free()


# ---------------------------------------------------------------------------
# four chips: the disaggregated layout
# ---------------------------------------------------------------------------

def _devices(tree):
    import jax
    ids = set()
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            ids |= {d.id for d in leaf.devices()}
    return sorted(ids)


def _greedy(params, model, prompts, mesh):
    from repro.core import RolloutEngine
    from repro.core.config import EngineConfig
    from repro.serve import Gateway

    # eos_id -1 is never sampled: every request decodes all 32 tokens,
    # where random weights may pick EOS at once
    eng = RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=4, prompt_len=24, max_gen_len=32, temperature=0.0,
        cache="paged", prefill_chunk=16, eos_id=-1))
    eng.place(mesh)
    gw = Gateway(eng)
    rids = [gw.submit(p) for p in prompts]
    gw.run_until_idle()
    return [gw.drain(r)["end"] for r in rids]


def four_chip_phase(cfg=None, **overrides):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.data import tokenizer
    from repro.launch.disaggregated import push_weights

    with Phase("four_chips") as ph:
        ph.check(len(jax.devices()) == 4, f"{len(jax.devices())} devices")
        ctl, trainer = _rl_run(ph, cfg or rl_config(), **overrides)
        roll_mesh = ctl.rollout_mesh
        ph.check(roll_mesh is not None, "the run was not disaggregated")
        roll = sorted(d.id for d in roll_mesh.devices.flat)
        train = _devices(trainer.params)
        # one micro-batch packed by the trainer itself
        seq = {"tokens": tokenizer.encode("1+2=3", bos=True, eos=True),
               "loss_mask": [1.0] * 7, "behav_logprob": [0.0] * 7,
               "advantage": 1.0}
        batch = trainer._pack_microbatches([seq])[0]
        eng = ctl.engine
        place = {"rollout_mesh": roll, "engine.params": _devices(eng.params),
                 "engine.cache": _devices(eng.cache),
                 "engine.slot_arrays": {
                     k: (_devices(v) if isinstance(v, jax.Array)
                         else type(v).__name__)
                     for k, v in sorted(vars(eng).items())
                     if isinstance(v, (jax.Array, np.ndarray))},
                 "trainer.params": train,
                 "trainer.opt_state": _devices(trainer.opt_state),
                 "trainer.batch": _devices(batch)}
        ph.out["placement"] = place
        ph.check(place["engine.params"] == roll and place["engine.cache"] == roll,
                 "engine state is not on the rollout submesh")
        ph.check(not set(train) & set(roll) and _devices(batch) == train,
                 "trainer state or batches are not on the trainer chip")

        # push_weights round trip: bit-exact on every rollout chip
        host = jax.device_get(trainer.params)
        pushed = push_weights(trainer.params, roll_mesh)
        exact = {}
        for leaf, ref in zip(jax.tree.leaves(pushed), jax.tree.leaves(host)):
            for shard in leaf.addressable_shards:
                got = np.asarray(shard.data)
                exact[shard.device.id] = exact.get(shard.device.id, True) and (
                    got.tobytes() == np.asarray(ref).tobytes())
        ph.out["push_bit_exact"] = exact
        ph.check(sorted(exact) == roll and all(exact.values()),
                 "push_weights round trip is not bit-exact")

        # greedy decode: rollout submesh vs one chip, same params
        model = eng.model
        prompts = [tokenizer.encode(f"{a}+{b}=", bos=True)
                   for a, b in ((1, 2), (3, 4), (5, 6), (7, 8))]
        sub = _greedy(pushed, model, prompts, roll_mesh)
        chip = {d.id: d for d in jax.devices()}[roll[0]]
        one = _greedy(host, model, prompts,
                      Mesh(np.array([[chip]]), ("data", "model")))
        same = [a["tokens"] == b["tokens"] for a, b in zip(sub, one)]
        dlp = max((float(np.max(np.abs(np.subtract(a["logprobs"],
                                                   b["logprobs"])),
                                initial=0.0))
                   for a, b in zip(sub, one) if a["tokens"] == b["tokens"]),
                  default=None)
        ph.out.update(submesh_decode_equal=same, submesh_max_abs_dlogprob=dlp,
                      submesh_tokens=[len(a["tokens"]) for a in sub])
        ph.check(all(same), "submesh decode differs from one-chip decode")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the disaggregated layout on a 4-chip host")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2

    from repro.launch import cli
    cache_dir = cli.enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    print(json.dumps({"compile_cache": cache_dir,
                      "jax": jax.__version__}), flush=True)
    if args.four_chips:
        four_chip_phase()
    else:
        kernel_phase()
        serve_phase()
        rl_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
