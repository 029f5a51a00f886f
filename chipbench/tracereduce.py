"""From a profiler trace to the benchmark's device numbers.

``load`` flattens an ``.xplane.pb`` into plain event records; ``reduce``
takes those records and the name of the benchmark's window annotation
and gives, for the stretch that annotation spans: each chip's busy
seconds (the union of its device operations), the idle gaps and the host
annotation each lies in, the device operations that took most time, and
the time of each kernel by name.  The reduction works on records alone,
so a small recorded trace checks it (``tests/test_tracereduce.py``).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

# Host annotations the benchmark writes; an idle gap is labelled by the
# innermost one that contains it.
HOST_PREFIXES = ("bench.", "rollout.", "trainer.", "gw.")
# Lines of a device plane that are not device operations.
NOT_OPS = ("Steps", "XLA Modules", "Framework Ops", "Framework Name Scope",
           "Source code", "XLA TraceMe", "Launch Stats")


def load(trace_dir: str) -> list:
    """Every event of the newest trace under ``trace_dir`` as a dict with
    plane, line, name, start and end (ns)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "start": float(e.start_ns),
                            "end": float(e.start_ns + e.duration_ns)})
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def device_ops(events: list) -> dict:
    """plane -> its device operations; where a plane has an "XLA Ops" line
    only that line counts, so an op is not counted twice."""
    by_plane = defaultdict(list)
    for e in events:
        if is_device(e["plane"]) and e["line"] not in NOT_OPS:
            by_plane[e["plane"]].append(e)
    out = {}
    for plane, evs in by_plane.items():
        lines = {e["line"] for e in evs}
        keep = "XLA Ops" if "XLA Ops" in lines else None
        out[plane] = [e for e in evs if keep is None or e["line"] == keep]
    return out


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    spans = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                   if b > lo and a < hi)
    merged = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def op_name(name: str) -> str:
    """An operation's name without its HLO text and numeric suffix:
    "%paged_decode_attention.7 = f32[...] custom-call(...)" reads
    "paged_decode_attention"."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


def self_times(evs, lo: float, hi: float):
    """(name, seconds) of each operation that starts in [lo, hi), less the
    time of the operations nested in it (a loop holds its body's ops)."""
    evs = sorted(evs, key=lambda e: (e["start"], -e["end"]))
    out, stack = [], []            # stack of [event, child time]
    def close(item):
        e, child = item
        if lo <= e["start"] < hi:
            out.append((op_name(e["name"]),
                        max(0.0, min(e["end"], hi) - e["start"] - child) / 1e9))
    for e in evs:
        while stack and stack[-1][0]["end"] <= e["start"]:
            close(stack.pop())
        if stack:
            stack[-1][1] += min(e["end"], stack[-1][0]["end"]) - e["start"]
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return out


def _labels(host, points):
    """For each of the sorted ``points``, the name of the shortest host
    annotation that contains it, else "none" (one sweep)."""
    host = sorted(host, key=lambda e: e["start"])
    out, active, i = [], [], 0
    for t in points:
        while i < len(host) and host[i]["start"] <= t:
            active.append(host[i])
            i += 1
        active = [e for e in active if e["end"] > t]
        best = min(active, key=lambda e: e["end"] - e["start"], default=None)
        out.append(best["name"] if best else "none")
    return out


def reduce(events: list, window: str = "bench.window", top: int = 10) -> dict:
    """Busy and idle time of every chip over the span of the host
    annotation ``window``, and what the host was doing in each idle gap."""
    spans = [e for e in events if e["name"] == window and not is_device(e["plane"])]
    if not spans:
        raise ValueError(f"no {window!r} annotation in the trace")
    lo, hi = spans[0]["start"], spans[0]["end"]
    host = [e for e in events if not is_device(e["plane"])
            and e["name"].startswith(HOST_PREFIXES) and e["name"] != window]
    ops = device_ops(events)
    if not ops:
        raise ValueError("no device operations in the trace")
    busy, idle_by_label = {}, defaultdict(float)
    kernel_s, kernel_n = defaultdict(float), defaultdict(int)
    for plane, evs in sorted(ops.items()):
        merged = union([(e["start"], e["end"]) for e in evs], lo, hi)
        busy[plane] = sum(b - a for a, b in merged) / 1e9
        gaps, prev = [], lo
        for a, b in merged + [[hi, hi]]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        for (a, b), name in zip(gaps, _labels(host, [(a + b) / 2 for a, b in gaps])):
            idle_by_label[name] += (b - a) / 1e9
        for name, dur in self_times(evs, lo, hi):
            kernel_s[name] += dur / len(ops)
            kernel_n[name] += 1
    window_s = (hi - lo) / 1e9
    n = len(busy)
    lines = defaultdict(int)
    for e in events:
        lines[f"{e['plane']} | {e['line']}"] += 1
    return {
        "lines": dict(lines),
        "window_s": window_s,
        "busy_s": sum(busy.values()) / n,
        "busy_by_chip": busy,
        "kernel_s": dict(kernel_s), "kernel_calls": dict(kernel_n),
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in kernel_s.items()),
                                 key=lambda x: -x[1])[:top],
            "idle_gaps": sorted(([k, v / n] for k, v in idle_by_label.items()),
                                key=lambda x: -x[1])[:top],
        },
    }

