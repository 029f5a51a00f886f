"""Decode fast path: fused single-dispatch steps and self-speculative
multi-token rounds, with a per-family tokens/s-vs-roofline gap table.

Three arms, all on the SAME tiny model so CI smoke stays cheap:

* **fused** — the paged engine in its three dispatch modes (DESIGN.md
  §Fused decode tail): ``default`` (one jitted step returning sampled
  tokens), ``fused`` (same single dispatch through the hoisted
  block-table gather + fused attention/projection tail) and ``split``
  (logits and sampling as two dispatches — the measurement baseline).
  All three must produce bit-identical trajectories; the gate bands
  ``throughput_ratio`` (fused vs split — median of position-paired
  per-step wall ratios, the modes driven in step-level lockstep) >= 1.0
  and ``dispatches_per_step`` == 1.0 for fused.

* **spec** — self-speculative decoding (DESIGN.md §Self-speculative
  decoding) under *controlled acceptance*: the last unit's ``wo`` /
  ``w_down`` are zeroed, making it an identity on the residual stream,
  so the truncated draft pass agrees with the full model and every
  draft is accepted.  The spec engine must be trajectory-identical to
  the plain greedy engine on the SAME zeroed params, and
  ``accepted_tokens_per_step`` (committed tokens per member-dispatch,
  1.0 = plain decode) must clear its floor.

* **families** — measured decode tokens/s for one representative of
  each architecture family (transformer / RG-LRU / xLSTM) next to the
  analytic memory-bound roofline (weights + decode state re-read per
  token, ``launch/roofline.py::decode_gap_rows``).  The record names
  the device it ran on; off the chip the gap is "not measured", and the
  gate holds a CPU record to that label.

Results land in ``BENCH_decode_speed.json`` via ``bench_path`` (smoke
runs never clobber the committed full-run baseline).  Each timed mode
builds ONE engine and runs a warmup batch on it first: the engine's jit
wrappers are per-instance, so a fresh engine per repeat would put
seconds of tracing — with far more variance than the ~5% steady-state
margin being gated — inside every timed window.  The timed batches then
reuse the warmed engine (pure steady-state dispatch); the fused arm
additionally drives its three modes in step-level lockstep and gates
the median of position-paired per-step wall ratios, so host drift and
background bursts hit both sides of every pair — the gated numbers are
*ratios* between modes, and timing the modes in separate blocks would
let background noise alone push them over a band.
"""
from __future__ import annotations

import json
import statistics
import time

from benchmarks.common import bench_path, emit

N_SLOTS = 4
PROMPT_LEN = 16
MAX_GEN = 16
N_REQUESTS = 24
SPEC_K = 4
# Smoke runs use the same counts as full runs: engine builds/compiles
# dominate this module's cost either way, and the timed steady-state
# batches are milliseconds — shrinking them only adds noise to the
# gated fused/split ratio.
REPEATS = 8


# The tiny tokenizer vocab (~50 ids) would make the split path's extra
# dispatch nearly free: the logits crossing the jit boundary are the
# traffic the fused tail exists to avoid, so the bench uses an LM-scale
# vocab (prompt ids stay inside the tokenizer range).
VOCAB = 8192


def _cfg(family: str = "dense"):
    from repro.configs.base import ModelConfig

    if family == "dense":
        return ModelConfig(name="bench-decode", family="dense", n_layers=3,
                           d_model=48, n_heads=4, n_kv_heads=2, d_ff=96,
                           vocab_size=VOCAB)
    if family == "hybrid":                 # RG-LRU + local attention
        return ModelConfig(name="bench-decode-rec", family="hybrid",
                           n_layers=2, d_model=48, n_heads=4, n_kv_heads=2,
                           d_ff=96, vocab_size=VOCAB,
                           block_pattern=("rec", "local"), local_window=8)
    return ModelConfig(name="bench-decode-xlstm", family="ssm", n_layers=2,
                       d_model=48, n_heads=4, n_kv_heads=4, d_ff=0,
                       vocab_size=VOCAB,
                       block_pattern=("mlstm", "slstm"))


def _build(cfg, seed: int = 0, **engine_kw):
    import jax

    from repro.core.config import EngineConfig
    from repro.core.rollout import RolloutEngine
    from repro.models.model import build_model

    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(seed))
    if engine_kw.pop("zero_last_unit", False):
        params = _zero_last_unit(params)
    eng = RolloutEngine(model, params, cfg=EngineConfig(
        n_slots=N_SLOTS, prompt_len=PROMPT_LEN, max_gen_len=MAX_GEN,
        seed=seed, rng="request", **engine_kw))
    return model, params, eng


def _zero_last_unit(params):
    """Zero the last stacked unit's attention output projection and MLP
    down-projection: with pre-norm residual blocks that unit becomes an
    identity on the residual stream, so the truncated draft pass (all
    units but the last) agrees with the full model exactly — controlled
    100% draft acceptance without changing any other unit."""
    units = []
    for blk in params["units"]:
        blk = dict(blk)
        if "attn" in blk:
            a = dict(blk["attn"])
            a["wo"] = a["wo"].at[-1].set(0.0)
            blk["attn"] = a
        if "mlp" in blk:
            m = dict(blk["mlp"])
            m["w_down"] = m["w_down"].at[-1].set(0.0)
            blk["mlp"] = m
        units.append(blk)
    out = dict(params)
    out["units"] = tuple(units)
    return out


def _requests(n, base: int = 0):
    return [{"rid": base + i, "prompt_id": base + i,
             "prompt": [1 + (5 * (base + i) + j) % 40
                        for j in range(PROMPT_LEN)],
             "answer": None} for i in range(n)]


def _drive(eng, n_requests: int, base: int = 0):
    """Run one request batch to completion on ``eng``.  Returns (wall_s,
    tokens, decode_steps, dispatches, responses, step_walls), all deltas
    for THIS batch: decode_steps counts engine steps that committed at
    least one token (a spec draft step commits none), and step_walls
    holds each such step's individual wall seconds."""
    done, decode_steps, step = 0, 0, 0
    pending = _requests(n_requests, base)
    responses = {}
    step_walls = []
    tokens0, dispatch0 = eng.tokens_generated, eng.decode_dispatches
    t0 = time.perf_counter()
    while done < n_requests:
        n = eng.admit(pending)
        pending = pending[n:]
        before = eng.tokens_generated
        t1 = time.perf_counter()
        finished = eng.step()
        dt = time.perf_counter() - t1
        if eng.tokens_generated > before:
            decode_steps += 1
            step_walls.append(dt)
        for f in finished:
            done += 1
            responses[f.rid] = tuple(f.response)
        step += 1
        assert step < 50_000, "decode benchmark did not converge"
    return (time.perf_counter() - t0, eng.tokens_generated - tokens0,
            decode_steps, eng.decode_dispatches - dispatch0, responses,
            step_walls)


def _record(wall, tokens, decode_steps, dispatches):
    return {
        "wall_s": round(wall, 4),
        "tokens": tokens,
        "throughput_tok_s": round(tokens / wall, 2),
        "decode_dispatches": dispatches,
        "dispatches_per_step": round(dispatches / max(1, decode_steps), 3),
    }


def _measure_many(cfg, n_requests: int, repeats: int, mode_kws: dict):
    """Build ONE engine per mode, run a warmup batch on it (the engine's
    per-instance jit wrappers trace here), then time ``repeats`` further
    request batches on the SAME engine and keep each mode's fastest —
    the timed region is pure steady-state dispatch, no tracing.  Batches
    are interleaved round-robin across modes and use the same request
    ids in every mode; with ``rng="request"`` a trajectory is a pure
    function of (seed, rid), so matching batches across modes must
    match bit-for-bit."""
    engines = {mode: _build(cfg, **kw)[2] for mode, kw in mode_kws.items()}
    for eng in engines.values():
        _drive(eng, n_requests)                          # warmup
    best = dict.fromkeys(mode_kws)
    resp = {mode: {} for mode in mode_kws}
    step_walls = {mode: [] for mode in mode_kws}
    for r in range(1, repeats + 1):
        for mode, eng in engines.items():
            wall, tokens, steps, dispatches, responses, walls = \
                _drive(eng, n_requests, base=r * n_requests)
            resp[mode].update(responses)
            step_walls[mode].extend(walls)
            if best[mode] is None or wall < best[mode][0]:
                best[mode] = (wall, tokens, steps, dispatches)
    out = {}
    for mode in mode_kws:
        rec = _record(*best[mode])
        rec["median_step_ms"] = round(
            statistics.median(step_walls[mode]) * 1e3, 4)
        out[mode] = (rec, resp[mode], engines[mode], step_walls[mode])
    return out


def _drive_lockstep(engines: dict, n_requests: int, base: int = 0):
    """Drive one request batch through every engine in step-level
    lockstep: mode A's step ``i`` runs microseconds before mode B's
    step ``i``, so position-paired timings share the same host
    conditions (CPU frequency, cache pressure, background load) and a
    paired ratio cancels drift that defeats any comparison of
    per-mode aggregates taken seconds apart.  All modes follow the
    identical deterministic schedule, so positions align exactly.
    Returns per mode: (wall_s, tokens, decode_steps, dispatches,
    responses, step_walls), deltas for THIS batch."""
    state = {mode: {"pending": _requests(n_requests, base), "done": 0,
                    "walls": [], "resp": {}, "wall": 0.0, "steps": 0,
                    "tokens0": eng.tokens_generated,
                    "dispatch0": eng.decode_dispatches}
             for mode, eng in engines.items()}
    rounds = 0
    while any(s["done"] < n_requests for s in state.values()):
        for mode, eng in engines.items():
            s = state[mode]
            if s["done"] >= n_requests:
                continue
            t0 = time.perf_counter()
            n = eng.admit(s["pending"])
            before = eng.tokens_generated
            finished = eng.step()
            dt = time.perf_counter() - t0
            s["pending"] = s["pending"][n:]
            s["wall"] += dt
            if eng.tokens_generated > before:
                s["steps"] += 1
                s["walls"].append(dt)
            for f in finished:
                s["done"] += 1
                s["resp"][f.rid] = tuple(f.response)
        rounds += 1
        assert rounds < 50_000, "decode benchmark did not converge"
    return {mode: (s["wall"], engines[mode].tokens_generated - s["tokens0"],
                   s["steps"],
                   engines[mode].decode_dispatches - s["dispatch0"],
                   s["resp"], s["walls"])
            for mode, s in state.items()}


def _paired_step_ratio(num_rounds, den_rounds):
    """Median over every position-paired per-step wall ratio (hundreds
    of samples), the statistic robust enough to gate a few-percent
    systematic margin: a best-wall quotient compares two extreme order
    statistics, and unpaired medians drift with the host between the
    modes' runs."""
    return statistics.median(
        n / d
        for nr, dr in zip(num_rounds, den_rounds)
        for n, d in zip(nr, dr))


def _measure(cfg, n_requests: int, repeats: int, **engine_kw):
    return _measure_many(cfg, n_requests, repeats, {"_": engine_kw})["_"][:3]


def _fused_arm(n_requests: int, repeats: int):
    cfg = _cfg("dense")
    engines = {
        "default": _build(cfg, cache="paged")[2],
        "fused": _build(cfg, cache="paged", fused_decode="fused")[2],
        "split": _build(cfg, cache="paged", fused_decode="split")[2],
    }
    _drive_lockstep(engines, n_requests)                 # warmup
    best = dict.fromkeys(engines)
    resp = {m: {} for m in engines}
    round_walls = {m: [] for m in engines}
    for r in range(1, repeats + 1):
        out = _drive_lockstep(engines, n_requests, base=r * n_requests)
        for m, (wall, tokens, steps, dispatches, responses, walls) in \
                out.items():
            resp[m].update(responses)
            round_walls[m].append(walls)
            if best[m] is None or wall < best[m][0]:
                best[m] = (wall, tokens, steps, dispatches)
    modes = {}
    for m in engines:
        modes[m] = _record(*best[m])
        modes[m]["median_step_ms"] = round(statistics.median(
            w for rw in round_walls[m] for w in rw) * 1e3, 4)
    identical = resp["default"] == resp["fused"] == resp["split"]
    assert identical, "fused/split/default decode trajectories diverged"
    ratio = _paired_step_ratio(round_walls["split"], round_walls["fused"])
    return {
        **modes,
        "throughput_ratio": round(ratio, 3),
        "dispatches_per_step": modes["fused"]["dispatches_per_step"],
        "trajectories_identical": identical,
    }


def _spec_arm(n_requests: int, repeats: int):
    cfg = _cfg("dense")
    runs = _measure_many(cfg, n_requests, repeats, {
        "baseline": {"cache": "paged", "temperature": 0.0,
                     "zero_last_unit": True},
        "spec": {"cache": "paged", "temperature": 0.0,
                 "zero_last_unit": True, "spec_decode": SPEC_K},
    })
    base, base_resp, _ = runs["baseline"][:3]
    spec, spec_resp, eng = runs["spec"][:3]
    identical = base_resp == spec_resp
    assert identical, "speculative trajectories diverged from greedy baseline"
    return {
        "k": SPEC_K,
        "baseline": base,
        "spec": spec,
        "accepted_tokens_per_step": round(eng.accepted_tokens_per_step, 3),
        "draft_acceptance_rate": round(eng.draft_acceptance_rate, 3),
        "throughput_ratio": round(
            spec["throughput_tok_s"] / max(base["throughput_tok_s"], 1e-9), 3),
        "trajectories_identical": identical,
    }


def _family_arm(n_requests: int, repeats: int):
    import jax
    import jax.numpy as jnp

    from repro.launch.roofline import decode_gap_rows

    families = {}
    for fam, key in (("dense", "transformer"), ("hybrid", "rg-lru"),
                     ("ssm", "xlstm")):
        cfg = _cfg(fam)
        rec, _, eng = _measure(cfg, n_requests, repeats)
        model = eng.model
        param_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(eng.params))
        state = jax.eval_shape(
            lambda m=model: m.init_cache(1, PROMPT_LEN + MAX_GEN, jnp.float32))
        state_bytes = sum(
            x.size * jnp.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(state))
        families[key] = {
            **rec,
            "tokens_per_s": rec["throughput_tok_s"],
            "param_bytes": param_bytes,
            "state_bytes": state_bytes,
            "bytes_per_token": param_bytes + state_bytes,
        }
    for row in decode_gap_rows({"device": _device(), "families": families}):
        families[row["family"]]["roofline_tok_s"] = row["roofline_tok_s"]
        families[row["family"]]["measured_over_roofline"] = \
            row["measured_over_roofline"]
    return families


def _device() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main() -> None:
    n_requests = N_REQUESTS
    repeats = REPEATS
    fused = _fused_arm(n_requests, repeats)
    spec = _spec_arm(n_requests, repeats)
    families = _family_arm(n_requests, repeats)
    record = {
        "device": _device(),
        "config": {"n_slots": N_SLOTS, "prompt_len": PROMPT_LEN,
                   "max_gen_len": MAX_GEN, "n_requests": n_requests,
                   "spec_k": SPEC_K, "repeats": repeats},
        "fused": fused,
        "spec": spec,
        "families": families,
    }
    with open(bench_path("BENCH_decode_speed.json"), "w") as f:
        json.dump(record, f, indent=2)

    emit("decode_fused_step",
         fused["fused"]["wall_s"] / max(fused["fused"]["tokens"], 1) * 1e6,
         f"tput_x{fused['throughput_ratio']:.2f}_vs_split")
    emit("decode_spec_accept",
         spec["spec"]["wall_s"] / max(spec["spec"]["tokens"], 1) * 1e6,
         f"accepted_per_step{spec['accepted_tokens_per_step']:.2f}")


if __name__ == "__main__":
    main()
