"""Readings that set the limits of a cell's comparison, on the chip.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \
        --variants program,control,half_batch,alter_token [--seconds 0]

For each seed, in one process: set-up of the cell and, with
``--seconds`` above 0, a window of that length (the training readings
need none), then per variant the numbers that ``run.py`` compares:

- ``program``: the program as the benchmark runs it (lower readings);
- ``control``: the reference computed in bfloat16, put in the program's
  place, against the float32 reference (an upper reading);
- ``half_batch``: the trainer steps on half of each batch and takes the
  mean over that half;
- ``alter_token``: one token of every answer is changed where the engine
  produces it.

A step that leaves the state unchanged reads 1 on ``change_gap`` by
construction and needs no run.  One JSON line per (seed, variant).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def readings(drv, c, t, seed, seconds, fault, with_control):
    """Program readings of one seed (and the control's, when asked)."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    sut = drv.RL(c, t, seed, fault=fault)
    sut.setup()
    n = sut.window(seconds)["last_step"] if seconds > 0 else len(sut.rec.steps)
    rec, w0 = sut.rec, sut.w0_host
    sut.free()
    del sut
    gc.collect()
    got = drv.program_readings(rec, w0, n)
    del w0
    t1 = time.perf_counter()
    ref = drv.reference_readings(c, t, seed, rec, n, np.float32)
    t2 = time.perf_counter()
    out = [{"seed": seed, "variant": fault or "program", "steps": n,
            **drv.gaps(got, ref), "losses": got["losses"],
            "ref_losses": ref["losses"], "run_s": t1 - t0,
            "reference_s": t2 - t1}]
    if with_control:
        low = drv.reference_readings(c, t, seed, rec, n, jax.numpy.bfloat16)
        out.append({"seed": seed, "variant": "control", "steps": n,
                    **drv.gaps(low, ref), "reference_s": time.perf_counter() - t2})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window after set-up; 0 compares set-up's steps only")
    args = ap.parse_args(argv)
    import jax

    import run
    _, cell = run.spec(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache(jax)
    c = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    t = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    drv = run.load_module(HERE / "drivers" / f"{t['kind']}.py")
    variants = args.variants.split(",")
    for seed in [int(s) for s in args.seeds.split(",")]:
        for v in variants:
            if v == "control":
                continue
            for line in readings(drv, c, t, seed, args.seconds,
                                 "" if v == "program" else v,
                                 v == "program" and "control" in variants):
                print(json.dumps(line), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
