"""Roofline analysis over dry-run artifacts (EXPERIMENTS.md §Roofline).

Terms per (arch x shape x mesh), hardware = TPU v5e (``PEAKS``):

  compute    = FLOPs_per_device / peak            (197 bf16 TFLOP/s)
  memory     = HBM_bytes_per_device / bw          (819 GB/s)
  collective = collective_bytes_per_device / link (50 GB/s per ICI link)

FLOPs/bytes are the trip-count-corrected statics from hlo_analysis.py
(the compiled module is the per-device program, so they are per-device
already).  Collective bytes are result-shape bytes of every collective
in the per-device program — an upper bound on per-device link traffic
(all-gather receives ~ (n-1)/n of the result over links).

MODEL_FLOPS is the analytic 6*N_active*D (train) / 2*N_active*D
(prefill) / 2*N_active*B (decode); the ratio MODEL_FLOPS / (HLO_FLOPs *
chips) shows how much compiled compute is useful (remat + attention +
MoE capacity overhead push it below 1).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from dataclasses import dataclass
from typing import Dict, List


@dataclass(frozen=True)
class Peaks:
    flops: float          # dense bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: float      # HBM capacity
    link_bw: float        # bytes/s of one chip-to-chip link


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interchip interconnect
# (taken here as four links of 50 GB/s).  A kind that is not listed is
# an error, never a default.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                         link_bw=50e9),
}
# the chip the dry-run compiles for and the benchmark targets
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for a kind that is
    not in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    variant: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    useful_ratio: float
    temp_gb: float
    fits: bool
    status: str
    reason: str = ""
    rec: Dict = None

    @property
    def total_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def row_from_record(rec: Dict) -> RooflineRow:
    variant = "+".join(filter(None, [
        "vp" if rec.get("vocab_parallel") else "",
        rec.get("remat_policy", "none") if rec.get("remat_policy", "none") != "none" else "",
        "" if rec.get("fsdp", True) else "nofsdp",
        rec.get("extra", "")]))
    if rec.get("status") != "ok":
        return RooflineRow(rec["arch"], rec["shape"], rec.get("mesh", "?"),
                           variant, 0, 0, 0, "-", 0, 0, False,
                           rec.get("status", "?"),
                           rec.get("reason", rec.get("error", ""))[:120], rec)
    flops = rec["hlo"]["flops"]
    nbytes = rec["hlo"]["bytes"]
    coll = sum(v for k, v in rec["collectives"].items()
               if not k.endswith("_count"))
    n_dev = rec["n_devices"]
    hw = peaks(TARGET_KIND)
    c = flops / hw.flops
    m = nbytes / hw.hbm_bw
    x = coll / hw.link_bw
    dom = max((c, "compute"), (m, "memory"), (x, "collective"))[1]
    useful = rec["model_flops"] / max(flops * n_dev, 1e-9)
    mem = rec["memory"]
    dev_bytes = mem["temp_bytes"] + mem["argument_bytes"] + mem["output_bytes"] \
        - mem.get("alias_bytes", 0)
    return RooflineRow(rec["arch"], rec["shape"], rec["mesh"], variant,
                       c, m, x, dom, useful, mem["temp_bytes"] / 2**30,
                       dev_bytes <= hw.hbm_bytes, "ok", "", rec)


def load_rows(dirpath: str) -> List[RooflineRow]:
    rows = []
    for path in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        with open(path) as f:
            rows.append(row_from_record(json.load(f)))
    return rows


def bottleneck_hint(r: RooflineRow) -> str:
    if r.status != "ok":
        return r.reason
    if r.dominant == "compute":
        if r.useful_ratio < 0.5:
            return ("cut remat/dispatch overhead (useful ratio %.2f): "
                    "saveable-dots policy" % r.useful_ratio)
        return "compute-bound at useful ratio %.2f: near roofline; try larger per-device batch" % r.useful_ratio
    if r.dominant == "memory":
        return "HBM-bound: fuse/shrink intermediates, shard the largest resident tensor"
    return "collective-bound: reshard to cut the largest collective, overlap with compute"


def to_markdown(rows: List[RooflineRow]) -> str:
    out = ["| arch | shape | mesh | variant | compute s | memory s | collective s "
           "| bottleneck | useful | temp GB | fits | status |",
           "|---|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r.arch} | {r.shape} | {r.mesh} | {r.variant or 'base'} "
            f"| {r.compute_s:.3e} | {r.memory_s:.3e} | {r.collective_s:.3e} "
            f"| {r.dominant} | {r.useful_ratio:.2f} | {r.temp_gb:.1f} "
            f"| {'Y' if r.fits else 'N'} | {r.status} {r.reason} |")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# decode throughput vs roofline (DESIGN.md §Fused decode tail)
# ---------------------------------------------------------------------------

DECODE_FAMILIES = ("transformer", "rg-lru", "xlstm")


NOT_MEASURED = "not measured"


def decode_roofline_tokens_per_s(bytes_per_token: float,
                                 device_kind: str = TARGET_KIND) -> float:
    """Single-stream decode is memory-bound: every generated token
    re-reads the full weights plus the request's decode state (KV blocks
    for attention, the fixed recurrent state for RG-LRU/xLSTM), so the
    hardware ceiling is the HBM bandwidth / bytes_per_token."""
    return peaks(device_kind).hbm_bw / max(float(bytes_per_token), 1.0)


def decode_gap_rows(bench: Dict) -> List[Dict]:
    """Measured-vs-roofline decode throughput per architecture family.

    Consumes benchmarks/decode_speed.py output: ``device`` names the
    platform and kind the run took place on, and each ``families`` entry
    carries measured ``tokens_per_s`` plus the analytic
    ``bytes_per_token`` split into ``param_bytes`` (weights re-read
    every step) and ``state_bytes`` (the family's decode state — the
    term the family actually differentiates: growing KV for
    transformers, O(1) recurrent state for RG-LRU and xLSTM).  On a TPU
    the gap ``measured_over_roofline`` divides by that kind's ceiling,
    clamped to (0, 1]; a run on any other platform measured nothing on
    the chip, so its gap is ``"not measured"`` and the ceiling shown is
    the target chip's."""
    dev = bench.get("device", {})
    on_chip = dev.get("platform") == "tpu"
    kind = dev["kind"] if on_chip else TARGET_KIND
    rows = []
    for fam, f in sorted(bench.get("families", {}).items()):
        ceil = decode_roofline_tokens_per_s(f["bytes_per_token"], kind)
        rows.append({
            "family": fam,
            "measured_tok_s": f["tokens_per_s"],
            "roofline_tok_s": ceil,
            "roofline_kind": kind,
            "measured_over_roofline": (min(1.0, f["tokens_per_s"] / ceil)
                                       if on_chip else NOT_MEASURED),
            "dominant_bytes": ("weights" if f["param_bytes"]
                               >= f["state_bytes"] else "state"),
        })
    return rows


def decode_gap_report(bench: Dict) -> str:
    out = ["| family | measured tok/s | roofline tok/s | gap | dominant |",
           "|---|---|---|---|---|"]
    for r in decode_gap_rows(bench):
        gap = r["measured_over_roofline"]
        gap = gap if isinstance(gap, str) else f"{gap:.2e}"
        out.append(f"| {r['family']} | {r['measured_tok_s']:.1f} "
                   f"| {r['roofline_tok_s']:.3e} | {gap} "
                   f"| {r['dominant_bytes']} |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="runs/dryrun")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--decode-bench", default="",
                    help="path to BENCH_decode_speed.json: print the "
                         "per-family decode tokens/s-vs-roofline gap "
                         "table instead of the dry-run roofline")
    args = ap.parse_args()
    if args.decode_bench:
        with open(args.decode_bench) as f:
            print(decode_gap_report(json.load(f)))
        return
    rows = load_rows(args.dir)
    if args.markdown:
        print(to_markdown(rows))
        return
    for r in rows:
        print(f"{r.arch:22s} {r.shape:12s} {r.mesh:8s} {r.variant or 'base':12s} "
              f"C={r.compute_s:.2e} M={r.memory_s:.2e} X={r.collective_s:.2e} "
              f"dom={r.dominant:10s} useful={r.useful_ratio:5.2f} "
              f"temp={r.temp_gb:6.1f}GB fits={'Y' if r.fits else 'N'} {r.status}"
              + (f" ({r.reason})" if r.reason else ""))
        if r.status == "ok":
            print(f"    -> {bottleneck_hint(r)}")


if __name__ == "__main__":
    main()
