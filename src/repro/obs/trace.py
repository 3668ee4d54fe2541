"""Near-zero-overhead phase tracing and named counters.

The paper's headline claims (9x speedup via orbital scheduling, 768-config
sweep feasibility) are statements about *where time goes*; the sim stack
reports simulation-time metrics (`RoundRecord`) but historically had no
visibility into real wall-clock cost — plan builds, jit compiles, routing,
cache hits. This module is the registry those phases report into.

Design constraints, in order:

1. **Default-off, bitwise-safe.** The global tracer starts disabled; a
   disabled `span(...)` is one module-global load plus a shared no-op
   context manager (no allocation, no clock read), and a disabled
   `count(...)` is one load + one branch. Untraced runs execute the exact
   same numeric code — tracing never touches values, only observes walls.
2. **Thread-safe.** Spans nest per-thread (a `threading.local` stack);
   finished events and counters are appended/merged under one lock.
3. **One clock per span.** A live span reads `time.perf_counter()` only
   (monotonic, immune to NTP steps); its wall time is the tracer's
   `t0_wall` plus its offset, for correlating with external logs.
4. **On the profiler's clock.** While a tracer is installed each span
   also opens a `jax.profiler.TraceAnnotation` of the same name on its
   own thread, so a profiler session (`jax.profiler.start_trace`) stamps
   the program's spans on its host plane, beside the device ops. With
   no profiler session running an annotation records nothing. jax is
   imported when a tracer is made, never by importing this module.
5. **Observing is not blocking.** `Tracer.sync` (default True) asks the
   instrumented code to wait for the device inside its spans, so span
   walls include device time; with `sync=False` tracing never waits on
   the device and never reads a device value (`syncing()`).

Usage::

    from repro.obs import span, count, enable, metrics_summary

    enable()                    # or enable(sync=False): never block
    with span("sim.round", idx=3):
        with span("sim.select"):
            ...
        count("comms.routes")
    metrics_summary()  # {"counters": ..., "spans": ..., ...}

Exporters (Chrome/Perfetto trace.json, flat JSONL) live in
`repro.obs.export`.
"""
from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time


class _NullSpan:
    """Shared no-op span: what `span()` returns while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):  # attribute attach is a no-op when disabled
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span. Created only while tracing is enabled."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_depth", "_id",
                 "_parent", "_note")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args

    def set(self, **args) -> None:
        """Attach/override span attributes after entry."""
        self.args.update(args)

    def __enter__(self):
        tracer = self._tracer
        stack = tracer._stack()
        self._depth = len(stack)
        self._parent = stack[-1]._id if stack else None
        self._id = next(tracer._ids)
        stack.append(self)
        self._note = tracer._annotation(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._note.__exit__(None, None, None)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        self._tracer._record(self, t1)
        return False


class Tracer:
    """Event + counter registry for one tracing session.

    `sync` is what `syncing()` reports while this tracer is installed:
    whether instrumented code may wait on the device to time it."""

    def __init__(self, max_events: int = 1_000_000, sync: bool = True):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count()  # open order; next() is atomic
        self.sync = bool(sync)
        self.max_events = int(max_events)
        self.events: list[dict] = []   # finished spans, completion order
        self.counters: dict[str, float] = {}
        self.dropped_events = 0
        self.pid = os.getpid()
        # Session origin on both clocks: span timestamps are offsets from
        # t0_mono; t0_wall anchors them to the wall clock.
        self.t0_wall = time.time()
        self.t0_mono = time.perf_counter()

    # ----------------------------------------------------------- spans --
    def _stack(self) -> list:
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def _record(self, sp: _Span, t1: float) -> None:
        ts = sp._t0 - self.t0_mono
        ev = {
            "name": sp.name,
            "ts_us": ts * 1e6,
            "dur_us": (t1 - sp._t0) * 1e6,
            "t_wall": self.t0_wall + ts,
            "tid": threading.get_ident(),
            "depth": sp._depth,
            "id": sp._id,            # this span, numbered in open order
            "parent": sp._parent,    # the enclosing span's id, or None
            "args": sp.args,
        }
        with self._lock:
            if len(self.events) < self.max_events:
                self.events.append(ev)
            else:
                self.dropped_events += 1

    # -------------------------------------------------------- counters --
    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def counter(self, name: str) -> float:
        """A counter's current value (0 before its first bump)."""
        with self._lock:
            return self.counters.get(name, 0)

    # --------------------------------------------------------- summary --
    def summary(self) -> dict:
        """Counters + per-phase wall-clock aggregates (+ hit rates derived
        from every `X.hit`/`X.miss` counter pair)."""
        with self._lock:
            events = list(self.events)
            counters = dict(self.counters)
            dropped = self.dropped_events
        spans: dict[str, dict] = {}
        for ev in events:
            s = spans.setdefault(ev["name"],
                                 {"count": 0, "total_s": 0.0, "max_s": 0.0})
            d = ev["dur_us"] / 1e6
            s["count"] += 1
            s["total_s"] += d
            s["max_s"] = max(s["max_s"], d)
        for s in spans.values():
            s["total_s"] = round(s["total_s"], 6)
            s["max_s"] = round(s["max_s"], 6)
        rates = {}
        for name in list(counters):
            if name.endswith(".hit"):
                stem = name[: -len(".hit")]
                total = counters[name] + counters.get(stem + ".miss", 0)
                if total:
                    rates[stem + ".hit_rate"] = round(counters[name] / total,
                                                      4)
        out = {
            "counters": counters,
            "rates": rates,
            "spans": spans,
            "wall_s": round(time.perf_counter() - self.t0_mono, 3),
        }
        if dropped:
            out["dropped_events"] = dropped
        return out


# ------------------------------------------------------ global registry --
# One module-global tracer; `None` means disabled. The hot-path helpers
# (`span`, `count`) read it exactly once so a disabled call costs one
# global load + one branch.
_tracer: Tracer | None = None


def enable(max_events: int = 1_000_000, sync: bool = True) -> Tracer:
    """Install (and return) a fresh global tracer."""
    global _tracer
    _tracer = Tracer(max_events=max_events, sync=sync)
    return _tracer


def disable() -> None:
    global _tracer
    _tracer = None


def enabled() -> bool:
    return _tracer is not None


def syncing() -> bool:
    """May instrumented code wait on the device (or read a device value)
    to time its span? True only while a tracer with `sync=True` is
    installed."""
    t = _tracer
    return t is not None and t.sync


def get_tracer() -> Tracer | None:
    return _tracer


def span(name: str, **args):
    """Context manager timing one phase (no-op while tracing is off)."""
    t = _tracer
    if t is None:
        return _NULL_SPAN
    return t.span(name, **args)


def count(name: str, n: float = 1) -> None:
    """Bump a named counter (no-op while tracing is off)."""
    t = _tracer
    if t is not None:
        t.count(name, n)


def metrics_summary() -> dict:
    """Summary of the global tracer ({} while tracing is off)."""
    t = _tracer
    return t.summary() if t is not None else {}


@contextlib.contextmanager
def tracing(max_events: int = 1_000_000, sync: bool = True):
    """Scoped tracing session (tests): enable, yield the tracer, restore
    whatever tracer — usually None — was installed before."""
    global _tracer
    prev = _tracer
    t = Tracer(max_events=max_events, sync=sync)
    _tracer = t
    try:
        yield t
    finally:
        _tracer = prev
