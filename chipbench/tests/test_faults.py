"""A run of the RL cell, at a tiny size on the CPU, with the timed path
broken underneath: ``correct`` has to come out false.  The look for a
chip is skipped (the driver is called as ``run.py`` calls it); the
limits are the cell's own (``limits/<cell>.json``).  The sound run
comes out true.

Faults: a trainer step that leaves the state unchanged; half of each
batch left out, the mean taken over the rest; a token altered where the
engine produces it.  The cell runs on one chip, so there is no exchange
between chips to leave out."""
import json
import time
from pathlib import Path

import pytest

import run

HERE = Path(__file__).parent
ROOT = HERE.parent
SEED = 2 ** 31 + 12345


def _run(driver, cfg, traffic, limits, seconds, fault):
    c = json.loads((HERE / cfg).read_text())
    t = json.loads((HERE / traffic).read_text())
    drv = run.load_module(ROOT / "drivers" / f"{driver}.py")
    return drv.run_cell(c, t, SEED, seconds, False, limits=limits,
                        t_start=time.perf_counter(), compiles=run.Compiles(),
                        trace_dir="", fault=fault)


@pytest.mark.parametrize("fault,correct", [
    ("", True), ("unchanged_state", False), ("half_batch", False),
    ("alter_token", False)])
def test_rl_cell(fault, correct):
    limits = json.loads((ROOT / "limits" / "qwen1.5b-4l.rl-grpo.json").read_text())
    res = _run("rl", "tiny_qwen2.json", "tiny_rl.json", limits, 2.0, fault)
    assert res["correct"] is correct, res["checks"]
    assert res["ctx"]["window_steps"] >= 1      # the window is compared too

