"""Attribution of a profiled stretch's idle device time to program layers.

The program's `repro.obs` spans are also profiler annotations while a
tracer is installed, so a profiled stretch run with tracing on (with
`sync=False`, so that tracing never waits on the device) has them on the
trace's host plane, on the device ops' clock. `attribute` reads such a
trace, in the plain-data form of `bench.trace.load`:

- The window and the driving thread are `bench.trace.reduce`'s: the span
  of the `WINDOW_EVENT` annotations, and the thread that wrote them.
  Other threads' events are left out.
- On the driving thread, a sweep over the edges of the spans named in
  `LAYERS` gives the layer of each instant: that of the innermost such
  span open then, or of an open span in `WHOLE`, which claims everything
  inside it. Spans not in the table (`comms.*`, `orbits.*`,
  `sim.batched.plan_scalar`, JAX's own host events) take the layer of
  the nearest enclosing span that is. An instant with none is `outside`:
  the benchmark's own code between calls.
- A chip is idle where none of its ops runs; each idle nanosecond in the
  window goes to the layer of its instant, so a gap that straddles two
  spans is split between them, and the layers sum to the idle time.
  Milliseconds are averaged over chips, as `reduce` averages busy time.
"""
from __future__ import annotations

import collections

from bench.trace import WINDOW_EVENT, merge

# span name -> layer; one table, reproduced in PERF.md section 3
LAYERS = {
    "sim.select": "select",
    "sim.batched.plan": "select",
    "sim.round": "round",
    "sim.batched.assemble": "round",
    "sim.client_train": "client_train",
    "sim.aggregate": "aggregate",
    "sim.eval": "eval",
    "sim.run": "run",
}
# spans whose layer covers every span inside them: the batched planner's
# own `sim.round` spans (mode `batched_plan`) are planning
WHOLE = frozenset({"sim.batched.plan"})
OUTSIDE = "outside"


def label_window(spans, lo: float, hi: float, layers=LAYERS,
                 whole=WHOLE) -> list[tuple[float, float, str]]:
    """[(start, end, layer)] in time order, covering [lo, hi] without
    overlap, from (name, start, duration) spans of one thread."""
    marks = []
    for i, (name, s, d) in enumerate(spans):
        if name in layers and d > 0:
            # At one instant: closes before opens, inner closes first,
            # outer opens first.
            marks.append((s, 1, -d, i))
            marks.append((s + d, 0, d, i))
    marks.sort()
    stack: list[int] = []
    out: list[tuple[float, float, str]] = []
    prev = lo

    def layer() -> str:
        for i in stack:                 # outermost first
            if spans[i][0] in whole:
                return layers[spans[i][0]]
        return layers[spans[stack[-1]][0]] if stack else OUTSIDE

    for t, opens, _, i in marks:
        t = min(max(t, lo), hi)
        if t > prev:
            out.append((prev, t, layer()))
            prev = t
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
    if hi > prev:
        out.append((prev, hi, layer()))
    return out


def _idle_by_layer(labels, busy, lo: float,
                   hi: float) -> collections.Counter:
    """Nanoseconds of [lo, hi] outside every `busy` interval (sorted,
    disjoint), per layer of `labels`."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    out: collections.Counter = collections.Counter()
    j = 0
    for gs, ge in gaps:
        while j < len(labels) and labels[j][1] <= gs:
            j += 1
        k = j
        while k < len(labels) and labels[k][0] < ge:
            s, e, name = labels[k]
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                out[name] += overlap
            k += 1
    return out


def attribute(trace: dict, layers=LAYERS, whole=WHOLE) -> dict | None:
    """Idle milliseconds of the window per layer, averaged over chips,
    with the window's seconds, busy seconds (mean over chips) and chips.
    Every layer of `layers` and `outside` is present. None where the
    trace holds no window or no device operation."""
    marks = [(s, s + d) for name, s, d, _ in trace["host"]
             if name == WINDOW_EVENT]
    driving = {th for name, _, _, th in trace["host"]
               if name == WINDOW_EVENT}
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not marks or not devices:
        return None
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    spans = [(name, s, d) for name, s, d, th in trace["host"]
             if th in driving and name in layers]
    labels = label_window(spans, lo, hi, layers, whole)
    idle: collections.Counter = collections.Counter()
    busy_ns = 0.0
    for ops in devices.values():
        busy = merge(((s, s + d) for _, s, d in ops), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        idle.update(_idle_by_layer(labels, busy, lo, hi))
    n = len(devices)
    names = list(dict.fromkeys(list(layers.values()) + [OUTSIDE]))
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
            "chips": n,
            "idle_ms": {k: idle.get(k, 0.0) / n / 1e6 for k in names}}
