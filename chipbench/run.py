"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``: the
configuration (``configs/<config>.json``), the traffic mix
(``traffic/<traffic>.json``, whose ``kind`` picks the driver in
``drivers/``), the limits of its comparison (``limits/<workload>.json``)
and the readers of its per-layer metrics (``metrics/<metric>.py``).
The configuration's ``model_type`` names its architecture
(``arch.py``): the reference module ``reference/<model_type>.py``
(leaves, plain forward, operation counts) and the program module
``programs/<model_type>.py`` (the system's config and parameter tree).

The run makes its weights and inputs from ``--seed``, warms up, measures
for about ``--seconds``, then compares what the timed path produced with
the plain reference and prints one JSON line last on standard output.
With ``--trace 1`` the window is traced and the per-layer metrics are
printed instead of the end-to-end ones.  It needs a TPU with as many
chips as the cell asks for, and prints no result without one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))


class Compiles:
    """Counts XLA compilations (the arithmetic of the backend-compile
    listener that the chip smoke test uses)."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def spec(workload: str) -> tuple:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    return bench, cell


def load_module(path: Path):
    s = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def metrics_of(bench, cell, kind: str, ctx: dict) -> dict:
    """The cell's end-to-end (``kind="end_to_end"``) or per-layer metrics,
    each from its reader; a reader that finds nothing leaves its metric out."""
    out = {}
    for m in bench[kind]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        if kind == "end_to_end":
            v = ctx["end_to_end"].get(m["name"])
        else:
            v = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it itself), else ``<checkout>/.jax_cache``, a fixed path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell = spec(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    enable_compile_cache(jax)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    c = json.loads((HERE / "configs" / f"{cell['config']}.json").read_text())
    t = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((HERE / "limits" / f"{cell['name']}.json").read_text())
    driver = load_module(HERE / "drivers" / f"{t['kind']}.py")
    res = driver.run_cell(c, t, args.seed, args.seconds, bool(args.trace),
                          limits=limits, t_start=T_START, compiles=compiles,
                          trace_dir=str(ROOT / ".bench_trace" / cell["name"]))
    gc.collect()

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = metrics_of(bench, cell, kind, res["ctx"])
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": dev}
    if args.trace:
        red = res["ctx"]["trace"]
        print(json.dumps({"trace_lines": red["lines"]}), file=sys.stderr)
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = red["breakdown"]
    out["checks"] = res["checks"]
    print(json.dumps({k: res["ctx"][k] for k in (
        "window_compiles", "setup_steps", "window_steps", "compared_steps",
        "reference_s")}),
        file=sys.stderr)
    for name, chk in res["checks"].items():
        print(f"check {name}: {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
