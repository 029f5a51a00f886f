"""Benchmark-regression gate (the CI bench lane's last step).

Compares candidate BENCH_*.json files — produced by
``python -m benchmarks.run --smoke`` with ``REPRO_BENCH_OUT`` pointing at
a scratch dir (see ``benchmarks/common.bench_path``) — against the
committed baselines in the repo root, with a per-metric tolerance band.

Two kinds of checks, chosen per metric:

* **absolute floors/ceilings** (``min``/``max``/``equals``) for metrics
  that are structural claims of the system — the paged engine's
  slots-at-fixed-HBM ratio, the chunked engine's stall reduction, the
  threaded runtime demonstrating true overlap.  These hold in smoke mode
  and on noisy 2-core CI runners, so the bands are deliberately looser
  than the committed full-run numbers (a smoke run must not fail the
  gate for being small, only for REGRESSING).
* **baseline-relative bands** (``rel``) for metrics that are
  deterministic functions of the workload (allocator math), where smoke
  equals the full run and any drift is a real behavior change.

A missing candidate file, a missing metric, or a band violation fails
the gate (exit 1, one line per violation).  Stdlib only.

    python tools/check_bench.py --candidate /tmp/repro-bench [--baseline .]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List


def get_path(obj: Any, path: str) -> Any:
    """Resolve 'a.b[2].c' style metric paths."""
    cur = obj
    for part in path.replace("]", "").replace("[", ".").split("."):
        if part == "":
            continue
        if isinstance(cur, list):
            cur = cur[int(part)]
        else:
            cur = cur[part]
    return cur


# file -> list of metric specs.  Keys per spec:
#   path            dotted path into the candidate JSON
#   min / max       absolute band (structural floor/ceiling)
#   equals          exact expected value
#   rel             allowed |candidate - baseline| / |baseline| (compared
#                   against the committed baseline's value at `path`)
SPECS: Dict[str, List[Dict[str, Any]]] = {
    "BENCH_paged_cache.json": [
        # PR 2 acceptance: >= 2x concurrent slots at fixed HBM.
        {"path": "min_slots_ratio", "min": 2.0},
        # allocator math is deterministic and step-count independent:
        # smoke must reproduce the committed curve exactly (small float
        # slack for the ratio rounding).
        {"path": "curve[0].paged_slots", "rel": 0.0},
        {"path": "curve[5].paged_slots", "rel": 0.0},
    ],
    "BENCH_chunked_prefill.json": [
        # This PR's acceptance: >= 2x smaller max decode-stall...
        {"path": "stall_reduction_x", "min": 2.0},
        # ... at (loosely) equal throughput; chunked is usually FASTER
        # (it skips the padded full-width re-prefill) so only a floor.
        {"path": "throughput_ratio", "min": 0.7},
        # identity property: both modes sampled the SAME token sequences
        # (the benchmark compares full per-request responses, not counts)
        {"path": "trajectories_identical", "equals": True},
        {"path": "chunked.tokens", "rel": 0.0,
         "baseline_path": "monolithic.tokens", "same_file": "candidate"},
    ],
    "BENCH_async_overlap.json": [
        # threaded must not be SLOWER than forced-serial, even on noisy
        # 2-core runners (committed full-run number is ~1.66x).
        {"path": "throughput_ratio", "min": 1.0},
        {"path": "overlap_demonstrated", "equals": True},
        # ISSUE 10 acceptance: the traced re-run emits a timeline that
        # tools/trace_check.py validates, with at least one wall-clock-
        # concurrent rollout/trainer span pair (>100 on the committed
        # run — the overlap is visible in the artifact, not just the
        # throughput ratio).
        {"path": "trace.valid", "equals": True},
        {"path": "trace.concurrent_span_pairs", "min": 1},
    ],
    "BENCH_trace_overhead.json": [
        # ISSUE 10 acceptance: tracing-enabled serving throughput stays
        # within 5% of tracing-disabled on the identical tick-
        # deterministic workload (best-of-reps per mode).
        {"path": "throughput_ratio", "min": 0.95},
        # the traced mode really traced (a zero here means the gate
        # above compared two untraced runs)
        {"path": "traced.events_per_rep", "min": 1},
    ],
    "BENCH_reward_overlap.json": [
        # PR 5 acceptance: at the injected verifier latency, async
        # scoring (reward workers) sustains >= 1.5x the synchronous
        # inline-verification throughput — verification is pipelined
        # behind generation, not serialized into it.
        {"path": "throughput_ratio", "min": 1.5},
        # admission backpressure keeps the unscored backlog bounded
        {"path": "async.backlog_bounded", "equals": True},
        # the code-environment sandbox actually ran (CI smoke exercises
        # subprocess verification end-to-end)
        {"path": "code_env.completed", "equals": True},
        {"path": "code_env.sandbox_verifications", "min": 1},
    ],
    "BENCH_fleet_overlap.json": [
        # PR 6 acceptance: the 2-worker process fleet reproduces the
        # single-process ThreadedRuntime's trajectories bit-for-bit on
        # the same seed (per-request RNG + lr=0 frozen params).
        {"path": "equivalence.trajectories_identical", "equals": True},
        {"path": "equivalence.n_common", "min": 1},
        # a SIGKILLed worker's in-flight slots are requeued and training
        # completes with nothing lost or double-counted.
        {"path": "kill.completed", "equals": True},
        {"path": "kill.requeued", "min": 1},
        {"path": "kill.duplicates", "equals": 0},
        {"path": "kill.lost", "equals": 0},
        # floor only: process supervision + pipe transport must not
        # collapse throughput vs the threaded runtime (fleet pipelining
        # usually puts this well above 1 on multi-core hosts).
        {"path": "throughput_ratio", "min": 0.2},
    ],
    "BENCH_decode_speed.json": [
        # PR 8 acceptance: the fused single-dispatch decode step is
        # bit-identical to the default and split paths and at least as
        # fast as the two-dispatch split baseline (best-of-repeats).
        {"path": "fused.trajectories_identical", "equals": True},
        {"path": "fused.throughput_ratio", "min": 1.0},
        {"path": "fused.dispatches_per_step", "equals": 1.0},
        # greedy self-speculative decoding reproduces the plain greedy
        # engine's full token sequences, and under controlled 100%
        # draft acceptance commits strictly more than one token per
        # member-dispatch (1.0 = plain decode; k=4 full acceptance
        # would be 2.0, EOS/headroom truncation pulls it slightly down)
        {"path": "spec.trajectories_identical", "equals": True},
        {"path": "spec.accepted_tokens_per_step", "min": 1.05},
        {"path": "spec.draft_acceptance_rate", "min": 0.5},
        # the gate runs CPU records: a CPU run measured no roofline
        # share, and must say so instead of dividing by a chip's peak
        {"path": "device.platform", "equals": "cpu"},
        {"path": "families.transformer.measured_over_roofline",
         "equals": "not measured"},
        {"path": "families.rg-lru.measured_over_roofline",
         "equals": "not measured"},
        {"path": "families.xlstm.measured_over_roofline",
         "equals": "not measured"},
    ],
    "BENCH_serve_gateway.json": [
        # PR 9 acceptance: the Zipf session trace actually shares its
        # chained prompt prefixes through the paged pool (floor is far
        # below the committed ~0.9 so template tweaks don't flap it).
        {"path": "baseline.prefix_hit_rate", "min": 0.3},
        # the undersized-pool section must genuinely thrash the LRU ...
        {"path": "pressure.evictions", "min": 1},
        # ... and STILL complete everything: pool exhaustion degrades to
        # recompute, never to a permanently deferred request.
        {"path": "pressure.deferred_permanent", "equals": 0},
        {"path": "pressure.completed", "rel": 0.0},
        # recompute-on-miss is bit-exact, and the claim is non-vacuous
        # (the small pool really evicted prefixes that were re-requested)
        {"path": "recompute.trajectories_identical", "equals": True},
        {"path": "recompute.small_evictions", "min": 1},
        # the whole trace runs on the deterministic tick clock: latency
        # percentiles are held at zero drift vs the committed baseline
        {"path": "baseline.ttft_p50", "rel": 0.0},
        {"path": "baseline.ttft_p99", "rel": 0.0},
        {"path": "baseline.itl_p50", "rel": 0.0},
        {"path": "baseline.itl_p99", "rel": 0.0},
    ],
    "BENCH_weight_stream.json": [
        # PR 7 acceptance: unquantized streaming is bit-for-bit
        # trajectory-identical to a monolithic full-tree update at the
        # same step boundary, across ring/paged x monolithic/chunked.
        {"path": "identity.all_identical", "equals": True},
        {"path": "identity.ring_monolithic.n_finished", "min": 4},
        # tokens lost per update drop >= 2x under the fixed 1-chunk-per-
        # opportunity transport model, at no throughput cost; the whole
        # stall section is schedule-deterministic and single-threaded,
        # so its numbers are held at ZERO drift vs the committed run
        # (step counts are fixed even in smoke mode).
        {"path": "stall.tokens_lost_ratio", "min": 2.0},
        {"path": "stall.throughput_ratio", "min": 1.0},
        {"path": "stall.tokens_lost_delta_per_update", "equals": 0.0},
        {"path": "stall.tokens_lost_full_per_update", "rel": 0.0},
        {"path": "stall.chunks_full_per_update", "rel": 0.0},
        {"path": "stall.chunks_delta_per_update", "rel": 0.0},
        # publication-to-pickup latency (decode opportunities): zero
        # drift, and streamed pickup strictly inside the full transfer
        {"path": "stall.delta_latency_steps", "rel": 0.0},
        {"path": "stall.full_latency_steps", "rel": 0.0},
        # delta-q decodes within its own declared per-chunk tolerance
        # and IS lossy (the exact-XOR path is the identity section)
        {"path": "quantized.within_tolerance", "equals": True},
        {"path": "quantized.lossy", "equals": True},
        # the real executors: threaded full vs delta identical on lr=0,
        # and a worker SIGKILLed mid-stream leaves a fleet that finishes
        # with nothing lost/duplicated and bit-identical trajectories
        # (proof the torn partial version was never applied)
        {"path": "threaded.trajectories_identical", "equals": True},
        {"path": "threaded.streams_completed", "min": 1},
        # (requeue-on-kill >= 1 is gated by BENCH_fleet_overlap.json;
        # here the kill lands mid-stream, where the victim may have
        # already delivered everything it owed — the mid-stream-specific
        # invariant is that NO torn partial version is ever applied,
        # i.e. trajectories stay bit-identical.)
        {"path": "fleet_kill.killed", "equals": True},
        {"path": "fleet_kill.completed", "equals": True},
        {"path": "fleet_kill.duplicates", "equals": 0},
        {"path": "fleet_kill.lost", "equals": 0},
        {"path": "fleet_kill.trajectories_identical", "equals": True},
    ],
}


def check_file(name: str, specs: List[Dict[str, Any]], candidate_dir: Path,
               baseline_dir: Path, errors: List[str]) -> None:
    cpath = candidate_dir / name
    bpath = baseline_dir / name
    if not cpath.exists():
        errors.append(f"{name}: candidate missing ({cpath})")
        return
    if not bpath.exists():
        errors.append(f"{name}: committed baseline missing ({bpath})")
        return
    cand = json.loads(cpath.read_text())
    base = json.loads(bpath.read_text())
    for spec in specs:
        path = spec["path"]
        try:
            val = get_path(cand, path)
        except (KeyError, IndexError, TypeError):
            errors.append(f"{name}: metric '{path}' missing from candidate")
            continue
        if "equals" in spec and val != spec["equals"]:
            errors.append(f"{name}: {path} = {val!r}, expected "
                          f"{spec['equals']!r}")
        if "min" in spec and not (isinstance(val, (int, float))
                                  and val >= spec["min"]):
            errors.append(f"{name}: {path} = {val!r} below floor "
                          f"{spec['min']}")
        if "max" in spec and not (isinstance(val, (int, float))
                                  and val <= spec["max"]):
            errors.append(f"{name}: {path} = {val!r} above ceiling "
                          f"{spec['max']}")
        if "rel" in spec:
            ref_obj = cand if spec.get("same_file") == "candidate" else base
            ref_path = spec.get("baseline_path", path)
            try:
                ref = get_path(ref_obj, ref_path)
            except (KeyError, IndexError, TypeError):
                errors.append(f"{name}: reference metric '{ref_path}' missing")
                continue
            denom = max(abs(float(ref)), 1e-12)
            drift = abs(float(val) - float(ref)) / denom
            if drift > spec["rel"] + 1e-12:
                errors.append(
                    f"{name}: {path} = {val!r} drifted {drift:.3%} from "
                    f"{ref!r} (allowed {spec['rel']:.3%})")


def run(candidate_dir: Path, baseline_dir: Path,
        specs: Dict[str, List[Dict[str, Any]]] = SPECS) -> List[str]:
    errors: List[str] = []
    for name, file_specs in specs.items():
        check_file(name, file_specs, candidate_dir, baseline_dir, errors)
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidate", required=True,
                    help="dir holding the smoke run's BENCH_*.json "
                         "(the REPRO_BENCH_OUT scratch dir)")
    ap.add_argument("--baseline", default=".",
                    help="dir holding the committed baselines (repo root)")
    args = ap.parse_args(argv)
    errors = run(Path(args.candidate), Path(args.baseline))
    for e in errors:
        print(f"BENCH REGRESSION: {e}", file=sys.stderr)
    if not errors:
        n = sum(len(v) for v in SPECS.values())
        print(f"check_bench: {n} metric bands over {len(SPECS)} files OK")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
