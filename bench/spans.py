"""Sums over the program's `repro.obs` spans, for the metric readers.

A span is the tracer's event dict: name, ts_us, dur_us, tid, depth, args.
"""
from __future__ import annotations


def _inside(a: dict, b: dict) -> bool:
    """Span a lies within span b, on b's thread."""
    return (a["tid"] == b["tid"] and a["depth"] > b["depth"]
            and a["ts_us"] >= b["ts_us"]
            and a["ts_us"] + a["dur_us"] <= b["ts_us"] + b["dur_us"])


def total_ms(spans: list[dict], names, where=None) -> float | None:
    """Milliseconds in spans named in `names` (and passing `where`), each
    counted once: a span inside another counted one is left out. None
    where no such span exists."""
    hits = [s for s in spans if s["name"] in names
            and (where is None or where(s))]
    if not hits:
        return None
    outer = [s for s in hits if not any(_inside(s, o) for o in hits
                                        if o is not s)]
    return sum(s["dur_us"] for s in outer) / 1e3


def self_ms(spans: list[dict], name: str, where=None) -> float | None:
    """Milliseconds in spans named `name` (and passing `where`) less their
    direct children."""
    own = [s for s in spans if s["name"] == name
           and (where is None or where(s))]
    if not own:
        return None
    total = 0.0
    for s in own:
        kids = [c for c in spans
                if c["depth"] == s["depth"] + 1 and _inside(c, s)]
        total += s["dur_us"] - sum(c["dur_us"] for c in kids)
    return total / 1e3
