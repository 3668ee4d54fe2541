"""Sums over the program's `sim.run` spans, for the traffic readers.

Each `sim.run` span (one whole `ConstellationSim.run()` or
`BatchedSweep.run()`) carries its run's host-device traffic as args:
`h2d_bytes`, `d2h_bytes` and `host_syncs`, the growth of the program's
counters of those names over the run. A run inside another run (a
planning twin of the batched sweep) is counted by the outer one already.
"""
from __future__ import annotations

RUN = "sim.run"


def run_total(spans: list[dict], arg: str) -> float | None:
    """`arg` summed over the outermost `sim.run` spans that carry it. None
    where no span carries it (a program without these args)."""
    by_id = {s["id"]: s for s in spans if "id" in s}

    def nested(s: dict) -> bool:
        p = by_id.get(s.get("parent"))
        while p is not None:
            if p["name"] == RUN:
                return True
            p = by_id.get(p.get("parent"))
        return False

    runs = [s for s in spans if s["name"] == RUN and arg in s["args"]]
    if not runs:
        return None
    return float(sum(s["args"][arg] for s in runs if not nested(s)))
