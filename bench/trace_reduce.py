"""Reduce one profiler trace (`.xplane.pb`) to the device's busy time, the
traced window, and a breakdown: the device operations that took the most
time, and the device's idle time by what the host was doing meanwhile.

Busy is the union of the intervals in which an operation ran on a device
(the `XLA Ops` line of each `/device:TPU:<i>` plane), averaged over the
devices traced.  The window runs from the first to the last event on the
host and device planes.  An idle stretch is named by the innermost host
annotation (`jax.profiler.TraceAnnotation`) open at its midpoint among
`names`, the benchmark's own spans and those it puts around the program's
callables; otherwise `host.other`.
"""
from __future__ import annotations

from pathlib import Path

TOP = 10


def _merge(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _ops_line(plane):
    lines = list(plane.lines)
    for ln in lines:
        if ln.name == "XLA Ops":
            return ln
    for ln in lines:
        if "Ops" in ln.name:
            return ln
    return None


def reduce_planes(planes, names=frozenset()) -> dict:
    """`planes`: objects with `.name` and `.lines`, each line with `.name`
    and `.events` (`.name`, `.start_ns`, `.duration_ns`), as
    `jax.profiler.ProfileData` gives them."""
    dev_ops: dict[str, float] = {}
    busy_by_dev, all_iv = [], []
    host_ann: list[tuple[float, float, str]] = []
    lo, hi = float("inf"), float("-inf")
    for plane in planes:
        if plane.name.startswith("/device:TPU"):
            line = _ops_line(plane)
            if line is None:
                continue
            iv = []
            for e in line.events:
                a, d = float(e.start_ns), float(e.duration_ns)
                iv.append((a, a + d))
                dev_ops[e.name] = dev_ops.get(e.name, 0.0) + d * 1e-9
            merged = _merge(iv)
            busy_by_dev.append(merged)
            all_iv += merged
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    a, d = float(e.start_ns), float(e.duration_ns)
                    lo, hi = min(lo, a), max(hi, a + d)
                    if e.name in names:
                        host_ann.append((a, a + d, e.name))
    if not busy_by_dev or not all_iv:
        return {"busy_s": 0.0, "window_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    lo = min(lo, min(a for a, _ in all_iv))
    hi = max(hi, max(b for _, b in all_iv))
    busy_s = sum(sum(b - a for a, b in m) for m in busy_by_dev) / len(
        busy_by_dev) * 1e-9
    # Idle stretches of the first device, named by the host's annotation.
    gaps, prev = [], lo
    for a, b in busy_by_dev[0]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if hi > prev:
        gaps.append((prev, hi))
    idle: dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        best = None
        for s, e, name in host_ann:
            if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        name = best[2] if best else "host.other"
        idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": busy_s, "window_s": (hi - lo) * 1e-9,
            "breakdown": {"device_ops": top(dev_ops), "idle_gaps": top(idle)}}


def reduce_file(path: str | Path, names=frozenset()) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes, names)


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(reduce_file(sys.argv[1], frozenset(sys.argv[2:]))))
