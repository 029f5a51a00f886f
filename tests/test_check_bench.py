"""Benchmark-regression gate (tools/check_bench.py): the committed
baselines pass their own bands, and a synthetic regression demonstrably
fails the gate (the acceptance criterion for the CI bench lane)."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "tools"))

import check_bench  # noqa: E402


def _copy_baselines(dst: Path):
    for name in check_bench.SPECS:
        shutil.copy(ROOT / name, dst / name)


def test_committed_baselines_pass_their_own_bands(tmp_path):
    """The committed full-run numbers satisfy every band (if this fails,
    either a benchmark regressed or a band is mis-set)."""
    _copy_baselines(tmp_path)
    assert check_bench.run(tmp_path, ROOT) == []


def test_synthetic_regression_fails(tmp_path):
    """Degrading the chunked-prefill stall metric below its floor makes
    the gate exit nonzero — the gate demonstrably catches regressions."""
    _copy_baselines(tmp_path)
    name = "BENCH_chunked_prefill.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["stall_reduction_x"] = 1.0          # chunking stopped helping
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("stall_reduction_x" in e for e in errors)
    assert check_bench.main(["--candidate", str(tmp_path),
                             "--baseline", str(ROOT)]) == 1


def test_identity_violation_fails(tmp_path):
    """The chunked-vs-monolithic trajectory identity is a gated metric
    (full-sequence flag AND token counts)."""
    _copy_baselines(tmp_path)
    name = "BENCH_chunked_prefill.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["trajectories_identical"] = False
    rec["chunked"]["tokens"] = rec["monolithic"]["tokens"] + 5
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("trajectories_identical" in e for e in errors)
    assert any("chunked.tokens" in e for e in errors)


def test_missing_candidate_file_fails(tmp_path):
    """A smoke lane that silently skipped a benchmark cannot pass."""
    _copy_baselines(tmp_path)
    (tmp_path / "BENCH_paged_cache.json").unlink()
    errors = check_bench.run(tmp_path, ROOT)
    assert any("candidate missing" in e for e in errors)


def test_missing_metric_fails(tmp_path):
    """A benchmark that dropped a gated metric cannot pass."""
    _copy_baselines(tmp_path)
    name = "BENCH_async_overlap.json"
    rec = json.loads((tmp_path / name).read_text())
    del rec["throughput_ratio"]
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("throughput_ratio" in e and "missing" in e for e in errors)


def test_deterministic_drift_fails(tmp_path):
    """Allocator-curve metrics are baseline-relative with zero band:
    any drift in the deterministic admission math is flagged."""
    _copy_baselines(tmp_path)
    name = "BENCH_paged_cache.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["curve"][0]["paged_slots"] += 1
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("drifted" in e for e in errors)


def test_reward_overlap_regression_fails(tmp_path):
    """The async-reward floor (>=1.5x over synchronous scoring) and the
    backlog bound are gated metrics."""
    _copy_baselines(tmp_path)
    name = "BENCH_reward_overlap.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["throughput_ratio"] = 1.2          # async stopped paying off
    rec["async"]["backlog_bounded"] = False
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any(name in e and "throughput_ratio" in e for e in errors)
    assert any("backlog_bounded" in e for e in errors)


def test_weight_stream_identity_violation_fails(tmp_path):
    """The streaming-pickup identity (4-config matrix) and the torn-
    version invariant (fleet kill trajectories) are gated metrics."""
    _copy_baselines(tmp_path)
    name = "BENCH_weight_stream.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["identity"]["all_identical"] = False
    rec["fleet_kill"]["trajectories_identical"] = False
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("identity.all_identical" in e for e in errors)
    assert any("fleet_kill.trajectories_identical" in e for e in errors)


def test_weight_stream_stall_regression_fails(tmp_path):
    """Losing the >=2x tokens-lost reduction, paying throughput for it,
    or drifting the deterministic stall schedule all fail the gate."""
    _copy_baselines(tmp_path)
    name = "BENCH_weight_stream.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["stall"]["tokens_lost_ratio"] = 1.5        # streaming stopped paying
    rec["stall"]["throughput_ratio"] = 0.9         # ... and now costs tokens
    rec["stall"]["chunks_delta_per_update"] += 1   # schedule drifted
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("tokens_lost_ratio" in e for e in errors)
    assert any("throughput_ratio" in e and name in e for e in errors)
    assert any("chunks_delta_per_update" in e and "drifted" in e
               for e in errors)


def test_decode_speed_identity_violation_fails(tmp_path):
    """The fused-path and speculative trajectory identities are gated
    metrics — a fast path that changes sampled tokens cannot ship."""
    _copy_baselines(tmp_path)
    name = "BENCH_decode_speed.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["fused"]["trajectories_identical"] = False
    rec["spec"]["trajectories_identical"] = False
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("fused.trajectories_identical" in e for e in errors)
    assert any("spec.trajectories_identical" in e for e in errors)


def test_serve_gateway_regression_fails(tmp_path):
    """A wedged request under pool pressure, a vacuous recompute claim,
    a broken recompute identity, and TTFT drift all fail the gate."""
    _copy_baselines(tmp_path)
    name = "BENCH_serve_gateway.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["pressure"]["deferred_permanent"] = 2      # requests wedged
    rec["recompute"]["trajectories_identical"] = False
    rec["recompute"]["small_evictions"] = 0        # identity claim vacuous
    rec["baseline"]["ttft_p99"] += 3               # scheduling drifted
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("deferred_permanent" in e for e in errors)
    assert any("recompute.trajectories_identical" in e for e in errors)
    assert any("small_evictions" in e for e in errors)
    assert any("ttft_p99" in e and "drifted" in e for e in errors)


def test_decode_speed_regression_fails(tmp_path):
    """Losing the single-dispatch property, the fused>=split throughput
    floor, the >1 accepted-tokens-per-step win, or a CPU record claiming
    a roofline share all fail the gate."""
    _copy_baselines(tmp_path)
    name = "BENCH_decode_speed.json"
    rec = json.loads((tmp_path / name).read_text())
    rec["fused"]["dispatches_per_step"] = 2.0      # fusion silently undone
    rec["fused"]["throughput_ratio"] = 0.8         # fused slower than split
    rec["spec"]["accepted_tokens_per_step"] = 1.0  # speculation stopped paying
    rec["families"]["transformer"]["measured_over_roofline"] = 0.0065  # CPU
    (tmp_path / name).write_text(json.dumps(rec))
    errors = check_bench.run(tmp_path, ROOT)
    assert any("dispatches_per_step" in e for e in errors)
    assert any("fused.throughput_ratio" in e and "below floor" in e
               for e in errors)
    assert any("accepted_tokens_per_step" in e and "below floor" in e
               for e in errors)
    assert any("measured_over_roofline" in e and "expected 'not measured'" in e
               for e in errors)
