"""The comparison that decides `correct`.

Each number that `bench/limits/<cell>.json` gives a limit is compared:

- `first_gap` (loop cells): the first global update, the server's
  pseudo-gradient, by the worst leaf:
  | ||P1 - R0|| - ||R1 - R0|| | / max(||R1 - R0||, median leaf's), where
  P1 is the program's model after update 1, R1 the reference's, and R0
  the reference's initial model.
- `change_gap`: the same after `check_rounds` updates.
- `runs_differ`: window calls whose records, accuracy or final model
  differ from the checked call's in any bit (limit 0).
- `plan_differs`: rounds whose timing fields differ from the host loop's
  planning of the same scenario (batched and mesh cells; limit 0).

Leaves that the reference leaves all but unmoved (change under a
thousandth of the median leaf's) are left out of the gaps.
"""
from __future__ import annotations

import jax
import numpy as np

SKIP_BELOW = 1e-3


def _norms(a, b) -> dict[str, float]:
    """Per leaf path, ||a - b|| in float64."""
    flat = jax.tree_util.tree_flatten_with_path(a)[0]
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(x, np.float64) - np.asarray(y, np.float64)))
        for (k, x), y in zip(flat, jax.tree.leaves(b), strict=True)}


def leaf_gaps(program, ref, init) -> dict[str, float]:
    """Per leaf, the gap between the norms of two changes from `init`:
    | ||P - I|| - ||R - I|| | / max(||R - I||, median leaf's ||R - I||),
    for the leaves the reference moves (change at least `SKIP_BELOW` of the
    median leaf's)."""
    rc = _norms(ref, init)
    pc = _norms(program, init)
    med = float(np.median(list(rc.values())))
    return {k: abs(pc[k] - r) / max(r, med) for k, r in rc.items()
            if med > 0 and r >= SKIP_BELOW * med}


def numbers(checked: list[dict], refs: list) -> dict:
    """The compared numbers from the checked call and the reference runs.

    `checked[s]` maps an update count to the program's params after it and
    `refs[s]` is the reference's (init, params after each update),
    for scenario s. Each number is the worst over scenarios; a missing
    model reads infinite.
    """
    gaps: dict[str, list[float]] = {}
    for got, (init, after) in zip(checked, refs, strict=True):
        last = len(after)
        points = {"change": last}
        if last > 1 and 1 in got:
            points["first"] = 1
        for name, n in points.items():
            per = leaf_gaps(got[n], after[n - 1], init) if n in got else {}
            gaps.setdefault(f"{name}_gap", []).append(
                max(per.values(), default=float("inf")))
    return {k: max(v) for k, v in sorted(gaps.items())}


def verdict(values: dict, limits: dict) -> tuple[bool, list[dict]]:
    """(every number that has a limit is within it, one row per such
    number: name, value, limit). A limit without a number fails."""
    rows = [{"name": k, "value": values.get(k), "limit": lim}
            for k, lim in limits.items()]
    ok = all(r["value"] is not None and np.isfinite(r["value"])
             and r["value"] <= r["limit"] for r in rows)
    return ok, rows
