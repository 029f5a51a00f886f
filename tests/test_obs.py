"""Unified telemetry subsystem (DESIGN.md §Telemetry): tracer
inertness and clock injection, Perfetto export well-formedness (via the
same validator CI runs, tools/trace_check.py), the metrics registry's
Prometheus/JSON surfaces and stats absorption, the flight recorder's
shipping protocol, and the scheduler's publication-to-pickup stats."""
import json
import sys
import threading
from pathlib import Path

import pytest

from repro.core.scheduler import AsyncScheduler
from repro.configs.base import RLConfig
from repro.core.simulator import SimPromptStream
from repro.obs import export, metrics, trace
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import _NULL_SPAN, Tracer

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "tools"))

import trace_check  # noqa: E402


# ---------------------------------------------------------------------------
# Tracer: disabled-mode guarantee
# ---------------------------------------------------------------------------

def test_disabled_tracer_is_inert():
    """DESIGN.md §Disabled-mode guarantee: while disabled, span()
    returns ONE shared no-op object (no allocation), the installed
    clock is never read, and no buffer is created."""
    def poison():
        raise AssertionError("disabled tracer read the clock")

    tr = Tracer(enabled=False, clock=poison)
    s1 = tr.span("a", k=1)
    s2 = tr.span("b")
    assert s1 is s2 is _NULL_SPAN             # the shared singleton
    with s1:
        tr.instant("i", x=2)
        tr.counter("c", 3.0)
    assert tr.event_count() == 0
    assert tr.drain() == []


def test_disabled_tracer_spans_reach_the_profiler(profiled):
    """DESIGN.md §Telemetry: while a profiler session collects, a
    DISABLED tracer's spans are profiler annotations under their plain
    names (arguments ride as stats), nested as entered, and its own
    clock is still never read."""
    def poison():
        raise AssertionError("disabled tracer read the clock")

    tr = Tracer(enabled=False, clock=poison)
    with profiled.session():
        assert tr.active
        with tr.span("rollout.step", version=3):
            with tr.span("rollout.decode.prepare"):
                pass
            with tr.span("rollout.decode.wait"):
                pass
    assert not tr.active and tr.span("x") is _NULL_SPAN
    assert tr.event_count() == 0
    step, prep, wait = profiled.spans("rollout.")
    assert [step["name"], prep["name"], wait["name"]] == [
        "rollout.step", "rollout.decode.prepare", "rollout.decode.wait"]
    assert step["stats"] == {"version": 3}
    assert len({step["line"], prep["line"], wait["line"]}) == 1
    assert step["start"] <= prep["start"] <= prep["end"] <= wait["start"]
    assert wait["end"] <= step["end"]


def test_enabled_tracer_writes_both_sinks(profiled):
    tr = Tracer(enabled=True, clock=_fake_clock([1.0, 2.0, 4.0, 7.0]))
    with profiled.session():
        with tr.span("trainer.train_step", n=2):
            with tr.span("trainer.inner"):
                pass
    assert [(e[1], e[2], e[3]) for e in tr.drain()] == [
        ("trainer.train_step", 1.0, 6.0), ("trainer.inner", 2.0, 2.0)]
    outer, inner = profiled.spans("trainer.")
    assert (outer["name"], inner["name"]) == ("trainer.train_step",
                                              "trainer.inner")
    assert outer["stats"] == {"n": 2}
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]


def test_global_helpers_follow_configure():
    trace.configure(enabled=False)
    assert trace.span("x") is _NULL_SPAN
    assert trace.get().active is False


# ---------------------------------------------------------------------------
# Tracer: recording with an injected clock
# ---------------------------------------------------------------------------

def _fake_clock(times):
    it = iter(times)
    return lambda: next(it)


def test_span_nesting_and_duration_patching():
    tr = Tracer(enabled=True, clock=_fake_clock([1.0, 2.0, 5.0, 9.0]),
                actor="t")
    with tr.span("outer", version=3):        # enter: ts=1
        with tr.span("inner"):               # enter: ts=2, exit: 5-2
            pass
    # outer exit: 9-1
    evs = tr.drain()
    assert [(e[0], e[1], e[2], e[3]) for e in evs] == [
        ("X", "outer", 1.0, 8.0), ("X", "inner", 2.0, 3.0)]
    assert evs[0][6] == {"version": 3}
    assert tr.drain() == []                   # drain clears


def test_instant_counter_and_track_override():
    tr = Tracer(enabled=True, clock=_fake_clock([1.0, 2.0]), actor="gw")
    tr.set_track("lane-0")
    tr.instant("admit", rid=7)
    tr.counter("backlog", 4.0)
    evs = tr.drain()
    assert evs[0][:3] == ["i", "admit", 1.0]
    assert evs[0][4:6] == ["gw", "lane-0"]
    assert evs[1][0] == "C" and evs[1][3] == 4.0


def test_default_track_is_thread_name():
    tr = Tracer(enabled=True, clock=_fake_clock([0.0]))
    done = []

    def work():
        tr.instant("from-thread")
        done.append(True)

    t = threading.Thread(target=work, name="my-lane")
    t.start()
    t.join()
    assert done and tr.drain()[0][5] == "my-lane"


# ---------------------------------------------------------------------------
# Export: the validator CI runs accepts what the exporter emits
# ---------------------------------------------------------------------------

def _sample_events():
    tr = Tracer(enabled=True,
                clock=_fake_clock([0.1, 0.2, 0.3, 0.4, 0.5]), actor="a")
    tr.set_track("rollout")
    with tr.span("rollout.step", version=1):
        tr.instant("rollout.finished", n=2)
    tr.counter("staleness", 1.5)
    tr.set_actor("b")
    tr.instant("other-proc")
    return tr.drain()


def test_export_is_valid_and_typed():
    doc = export.chrome_trace(_sample_events())
    assert trace_check.validate(doc) == []
    evs = doc["traceEvents"]
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "rollout.step"
    assert x["ts"] == pytest.approx(0.1 * 1e6)    # seconds -> µs
    assert x["dur"] == pytest.approx(0.2 * 1e6)   # exit 0.3 - enter 0.1
    c = next(e for e in evs if e["ph"] == "C")
    assert c["args"] == {"value": 1.5}
    # actors -> pids with metadata; tracks -> tids with thread_name
    pnames = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    tnames = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert pnames == {"a", "b"} and "rollout" in tnames
    pids = {e["pid"] for e in evs if e["ph"] != "M"}
    assert len(pids) == 2                     # one per actor


def test_export_sorts_interleaved_buffers_monotone():
    """Two threads sharing a track name interleave; the exporter's
    global sort keeps per-(pid,tid) timestamps monotone (the property
    trace_check enforces)."""
    events = [["i", "a", 5.0, 0.0, "p", "lane", None],
              ["i", "b", 1.0, 0.0, "p", "lane", None],
              ["i", "c", 3.0, 0.0, "p", "lane", None]]
    doc = export.chrome_trace(events)
    assert trace_check.validate(doc) == []
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_write_trace_drains_global(tmp_path):
    trace.configure(enabled=True, clock=_fake_clock([1.0, 2.0]),
                    actor="w")
    trace.instant("only")
    p = tmp_path / "t.json"
    try:
        export.write_trace(str(p))
    finally:
        trace.configure(enabled=False)
    doc = json.loads(p.read_text())
    assert trace_check.validate(doc) == []
    assert trace.get().event_count() == 0     # drained


# ---------------------------------------------------------------------------
# trace_check: the validator actually catches malformed traces
# ---------------------------------------------------------------------------

def _ev(**kw):
    base = {"name": "e", "ph": "i", "ts": 0.0, "pid": 1, "tid": 1,
            "s": "t"}
    base.update(kw)
    return base


def test_trace_check_catches_non_monotonic_track():
    doc = {"traceEvents": [_ev(ts=5.0), _ev(ts=1.0)]}
    assert any("non-monotonic" in e for e in trace_check.validate(doc))
    # different tracks may interleave freely
    ok = {"traceEvents": [_ev(ts=5.0), _ev(ts=1.0, tid=2)]}
    assert trace_check.validate(ok) == []


def test_trace_check_catches_unbalanced_and_bad_spans():
    doc = {"traceEvents": [_ev(ph="B", name="open")]}
    assert any("never closed" in e for e in trace_check.validate(doc))
    doc = {"traceEvents": [_ev(ph="E", name="orphan")]}
    assert any("E without matching B" in e
               for e in trace_check.validate(doc))
    doc = {"traceEvents": [_ev(ph="X", dur=-1.0)]}
    assert any("bad dur" in e for e in trace_check.validate(doc))
    assert trace_check.validate({"traceEvents": "nope"}) \
        == ["top-level 'traceEvents' missing or not a list"]


def test_concurrent_span_pairs_counts_overlap():
    meta = [
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
         "args": {"name": "areal-rollout"}},
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 2,
         "args": {"name": "areal-trainer"}},
    ]
    spans = [
        _ev(ph="X", ts=0.0, dur=10.0, tid=1),   # rollout
        _ev(ph="X", ts=5.0, dur=10.0, tid=2),   # trainer: overlaps
        _ev(ph="X", ts=50.0, dur=1.0, tid=2),   # trainer: disjoint
    ]
    doc = {"traceEvents": meta + spans}
    assert trace_check.concurrent_span_pairs(doc, "rollout",
                                             "trainer") == 1
    assert trace_check.concurrent_span_pairs(doc, "rollout",
                                             "missing") == 0


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_histogram_le_bucket_semantics():
    h = metrics.Histogram("h", (1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 4.0, 99.0):      # 1.0 and 4.0 on bounds
        h.observe(v)
    assert h.cumulative() == [(1.0, 2), (2.0, 3), (4.0, 4),
                              (float("inf"), 5)]
    assert h.count == 5 and h.sum == pytest.approx(106.0)
    assert h.quantile(0.5) == 2.0
    assert h.quantile(0.99) == 4.0            # +Inf clamps to top bound
    with pytest.raises(ValueError, match="ascend"):
        metrics.Histogram("bad", (2.0, 1.0))


def test_registry_get_or_create_and_kind_conflict():
    reg = metrics.MetricsRegistry()
    c = reg.counter("x.count")
    c.inc()
    assert reg.counter("x.count") is c        # same object back
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x.count")


def test_absorb_flattens_and_skips_non_numeric():
    reg = metrics.MetricsRegistry()
    reg.absorb("engine", {"steps": 7, "nested": {"deep": 1.5},
                          "flag": True, "label": "skip-me"})
    snap = reg.snapshot()
    assert snap["engine.steps"] == 7.0
    assert snap["engine.nested.deep"] == 1.5
    assert snap["engine.flag"] == 1.0
    assert "engine.label" not in snap


def test_prometheus_text_format():
    reg = metrics.MetricsRegistry()
    reg.counter("gw.done", help="finished requests").inc(3)
    h = reg.histogram("gw.ttft", (1.0, 2.0))
    h.observe(1.5)
    txt = reg.prometheus_text()
    assert "# TYPE repro_gw_done counter" in txt
    assert "# HELP repro_gw_done finished requests" in txt
    assert "repro_gw_done 3" in txt
    assert 'repro_gw_ttft_bucket{le="2.0"} 1' in txt
    assert 'repro_gw_ttft_bucket{le="+Inf"} 1' in txt
    assert "repro_gw_ttft_count 1" in txt
    # snapshot is strict JSON even with +Inf-bucket samples
    h.observe(1e9)
    json.loads(reg.snapshot_json())


def test_scrape_unions_available_surfaces():
    class Obj:
        def stats(self):
            return {"a": 1, "b": 1}

        def stream_stats(self):
            return {"b": 2}                   # later surface wins

    out = metrics.scrape(Obj())
    assert out == {"a": 1, "b": 2}            # no publication_stats: skipped
    assert metrics.scrape(object()) == {}


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_recorder_capacity_and_incremental_drain():
    clk = _fake_clock([float(i) for i in range(20)])
    rec = FlightRecorder(capacity=4, clock=clk)
    rec.record("a", x=1)
    rec.record("b")
    first = rec.drain_new()
    assert [e[2] for e in first] == ["a", "b"]
    assert rec.drain_new() == []              # nothing new since
    for k in range(6):
        rec.record(f"k{k}")
    assert len(rec) == 4                      # bounded
    fresh = rec.drain_new()
    assert [e[2] for e in fresh] == ["k2", "k3", "k4", "k5"]


def test_recorder_extend_preserves_seq_and_dump(tmp_path):
    src = FlightRecorder(capacity=8, clock=_fake_clock([1.0, 2.0]))
    src.record("start", pid=42)
    src.record("admit", n=3)
    sup = FlightRecorder(capacity=8)
    sup.extend(src.drain_new())               # the heartbeat path
    assert len(sup) == 2
    assert "start pid=42" in sup.format_tail()
    assert FlightRecorder().format_tail() == "(empty)"
    p = tmp_path / "deep" / "dump.json"       # dump makedirs
    sup.dump(str(p))
    data = json.loads(p.read_text())
    assert [d["kind"] for d in data] == ["start", "admit"]
    assert data[0]["seq"] == 1 and data[0]["info"] == {"pid": 42}


# ---------------------------------------------------------------------------
# Scheduler publication stats (satellite: direct unit coverage)
# ---------------------------------------------------------------------------

def _sched():
    rl = RLConfig(batch_size=8, max_staleness=4, interruptible=True)
    return AsyncScheduler(prompt_stream=SimPromptStream(8), rl=rl)


def test_publication_stats_latency_accounting():
    s = _sched()
    assert s.publication_stats() == {
        "published": 0, "pickups": 0,
        "latency_mean": 0.0, "latency_max": 0.0}
    s.note_published(1, t=10.0)
    s.note_pickup(1, t=12.0, who="w0")
    s.note_pickup(1, t=16.0, who="w1")        # per-worker samples kept
    s.note_pickup(99, t=1.0)                  # unknown version ignored
    st = s.publication_stats()
    assert st["published"] == 1 and st["pickups"] == 2
    assert st["latency_mean"] == pytest.approx(4.0)
    assert st["latency_max"] == pytest.approx(6.0)
