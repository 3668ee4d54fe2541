"""Reduction of a JAX profiler trace to device busy time, idle share and
the breakdown.

The trace is read with `jax.profiler.ProfileData` and turned into plain
data first (`load`), so the reduction (`reduce`) runs on a small recorded
trace in the tests as it does on a chip's trace.

- Device planes are those named `/device:TPU:<n>`; their operations are
  the events of the line named `XLA Ops`, each named by its jitted
  program (the `XLA Modules` event around it) and its HLO instruction.
- The window is the span of the host annotations named `WINDOW_EVENT`
  (whole FL runs), on the trace's own clock.
- Busy is the union of a chip's operation intervals inside the window;
  the idle share is 1 minus busy over the window, averaged over chips.
- Each idle gap is named by the innermost host event open at its middle
  on the thread that drives the runs (the one that wrote `WINDOW_EVENT`):
  a jit dispatch, a transfer, ..., or "none".
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW_EVENT = "bench.run"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def op_name(hlo: str, module: str | None) -> str:
    """`<program>/<instruction> <result type>` from an op event's HLO text,
    e.g. `jit_update/%fusion.12 bf16[320,28,28]`."""
    head, _, rest = hlo.partition(" = ")
    short = f"{head} {rest.split('{')[0]}".strip()[:100]
    return f"{module}/{short}" if module else short


def _ops(plane) -> list[tuple[str, float, float]]:
    lines = {line.name: list(line.events) for line in plane.lines}
    mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                   e.name.split("(")[0]) for e in lines.get(MODULES_LINE, []))
    starts = [m[0] for m in mods]
    out = []
    for ev in lines.get(OPS_LINE, []):
        s = float(ev.start_ns)
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else None
        out.append((op_name(ev.name, mod), s, float(ev.duration_ns)))
    return out


def load(profile_dir: str) -> dict:
    """Plain-data view of the newest `.xplane.pb` under `profile_dir`:
    {"devices": {plane: [(op, start_ns, dur_ns), ...]},
     "host": [(name, start_ns, dur_ns, thread), ...]}."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = _ops(plane)
        elif plane.name == HOST_PLANE:
            host.extend((ev.name, float(ev.start_ns), float(ev.duration_ns),
                         line.name)
                        for line in plane.lines for ev in line.events
                        if ev.duration_ns > 0)
    return {"devices": devices, "host": host}


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_name(host, t: float) -> str:
    """Innermost (shortest) host event open at time t, or "none"."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "none"


def reduce(trace: dict) -> dict | None:
    """Busy seconds per chip, window seconds, idle share and breakdown.

    Returns None where the trace holds no window or no device operation:
    nothing to read.
    """
    marks = [(s, s + d) for name, s, d, _ in trace["host"]
             if name == WINDOW_EVENT]
    driver = {th for name, _, _, th in trace["host"] if name == WINDOW_EVENT}
    host = [(name, s, d) for name, s, d, th in trace["host"]
            if th in driver and name != WINDOW_EVENT]
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not marks or not devices:
        return None
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    window_ns = hi - lo
    busy, op_time = {}, collections.Counter()
    gaps: list[tuple[float, float]] = []
    for plane, ops in sorted(devices.items()):
        merged = merge(((s, s + d) for _, s, d in ops), lo, hi)
        busy[plane] = sum(e - s for s, e in merged)
        for name, s, d in ops:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                op_time[name] += clipped
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n = len(devices)
    busy_s = sum(busy.values()) / n / 1e9
    window_s = window_ns / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_gaps = [[_host_name(host, (s + e) / 2), (e - s) / 1e9]
                 for s, e in gaps[:TOP]]
    device_ops = [[name, t / n / 1e9] for name, t in op_time.most_common(TOP)]
    return {"busy_s": busy_s, "window_s": window_s, "chips": n,
            "idle_share": 1.0 - busy_s / window_s,
            "breakdown": {"device_ops": device_ops, "idle_gaps": idle_gaps}}
