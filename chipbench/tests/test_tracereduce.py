"""The trace reduction on small traces with known answers."""
import json
from pathlib import Path

import pytest

import tracereduce

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, end):
    return {"plane": plane, "line": line, "name": name, "start": start, "end": end}


def synthetic():
    return [
        ev(HOST, "main", "bench.window", 0, 100),
        ev(HOST, "rollout", "rollout.step", 0, 50),
        ev(HOST, "trainer", "trainer.train_step", 50, 100),
        ev(DEV, "XLA Ops", "paged_decode_attention", 10, 30),
        ev(DEV, "XLA Ops", "fusion.1", 30, 40),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[2] fusion(...)", 60, 70),
        ev(DEV, "XLA Ops", "%while.3 = (s32[]) while(...)", 60, 75),
        ev(DEV, "XLA Modules", "jit_step", 10, 70),     # not an op
        ev(DEV, "XLA Ops", "fusion.2", 95, 130),        # clipped at the window
    ]


def test_busy_idle_and_gaps():
    red = tracereduce.reduce(synthetic())
    assert red["window_s"] == pytest.approx(100e-9)
    # union: [10, 40) + [60, 75) + [95, 100) = 50 ns
    assert red["busy_s"] == pytest.approx(50e-9)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # [0, 10) in rollout.step; [40, 60) centred on 50, in trainer.train_step;
    # [75, 95) in trainer.train_step
    assert gaps == pytest.approx({"rollout.step": 10e-9, "trainer.train_step": 40e-9})
    ops = dict(red["breakdown"]["device_ops"])
    # names lose their HLO text and suffix, so the fusions add up (10 + 10
    # + 5 clipped); the loop keeps only the 5 ns its nested fusion leaves
    assert ops["fusion"] == pytest.approx(25e-9)
    assert ops["while"] == pytest.approx(5e-9)
    assert ops["paged_decode_attention"] == pytest.approx(20e-9)


def test_union_merges_and_clips():
    assert tracereduce.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 10) == [[1, 4], [5, 8], [9, 10]]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tracereduce.reduce([ev(DEV, "XLA Ops", "x", 0, 1)])


RECORDED = Path(__file__).parent / "data" / "v5e_trace_small.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_v5e_trace():
    """A trace recorded on one v5e chip: two 4096x4096 bf16 matmuls per
    annotated step, with host sleeps between the steps (see the file's
    "how" key).  The expected numbers were worked out from the events by
    hand and are stored beside them."""
    rec = json.loads(RECORDED.read_text())
    red = tracereduce.reduce(rec["events"])
    for key, want in rec["expected"].items():
        assert red[key] == pytest.approx(want, rel=1e-9), key
