"""The control: the plain reference computed one precision below the
configuration's (bfloat16 for float32), put in the program's place.

On a TPU this runs the RL cell's control at the cell's own size on three
seeds and asserts that every run fails at least one of the cell's limits
(the readings that set the limits are in PERF.md).  On the CPU it runs
the same path at a tiny size, where the cells' limits do not apply, and
asserts that the control reads far above the program on every number the
control is meant to fail."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import run

HERE = Path(__file__).parent
ROOT = HERE.parent
ON_TPU = jax.devices()[0].platform == "tpu"


def _files(cell):
    _, spec = run.spec(cell)
    c = json.loads((ROOT / "configs" / f"{spec['config']}.json").read_text())
    t = json.loads((ROOT / "traffic" / f"{spec['traffic']}.json").read_text())
    limits = json.loads((ROOT / "limits" / f"{cell}.json").read_text())
    return c, t, limits


def _rl(c, t, seed):
    import control
    drv = run.load_module(ROOT / "drivers" / "rl.py")
    _, low = control.readings(drv, c, t, seed, 0.0, "", True)
    return low


@pytest.mark.skipif(not ON_TPU, reason="the cells' own size needs a TPU")
@pytest.mark.parametrize("cell", ["qwen1.5b-4l.rl-grpo"])
@pytest.mark.parametrize("seed", [2 ** 31 + 101, 2 ** 31 + 102, 2 ** 31 + 103])
def test_control_fails_at_the_cells_size(cell, seed):
    c, t, limits = _files(cell)
    got = _rl(c, t, seed)
    assert any(got[k] > limits[k] for k in got if k in limits), got


def test_control_reads_far_above_the_program_at_a_tiny_size():
    import control
    drv = run.load_module(ROOT / "drivers" / "rl.py")
    c = json.loads((HERE / "tiny_qwen2.json").read_text())
    t = json.loads((HERE / "tiny_rl.json").read_text())
    prog, low = control.readings(drv, c, t, 3, 2.0, "", True)
    assert prog["steps"] > t["setup_steps"]     # the window's steps too
    for k in ("logprob_gap", "moment_gap", "change_gap"):
        assert low[k] > 100 * prog[k], (k, low[k], prog[k])
