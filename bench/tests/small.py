"""A cell cut to a size a CPU test run holds, driven through the
harness with the look for a chip skipped, and the faults that break the
program's timed path underneath it."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench import harness


def small_spec(cell: str, *, max_steps: int | None = None,
               mix: str | None = None) -> dict:
    """Cell `cell` on 10 satellites (c2s5), a short horizon and a few
    updates, under mix `mix` (`bench/traffic/<mix>.json`) if given; its
    limits are the cell's own."""
    spec = harness.load_spec(cell)
    if mix is not None:
        spec["mix"] = harness._json(harness.BENCH, "traffic", mix + ".json")
    mix = spec["mix"]
    for s in mix["scenarios"]:
        s["clusters"], s["sats"] = 2, 5
    if mix["executor"] == "batched":
        mix["scenarios"] = mix["scenarios"][:2]
    else:
        mix["horizon_days"] = 2
    mix["rounds"] = mix["check_rounds"] = 3
    if max_steps is not None:
        mix["max_steps"] = max_steps
    return spec


def run(spec: dict, seed: int = 2 ** 31 + 5) -> dict:
    return harness.run_cell(spec, seed, 0.05, False,
                            t0=time.perf_counter(), require_tpu=False,
                            log=lambda msg: None)


# --------------------------------------------------------------- faults --
def _updater_builder(orig, wrap):
    def build(*args, **kwargs):
        return wrap(orig(*args, **kwargs))
    return build


def state_unchanged(mp):
    """Every client's local step returns its state unchanged."""
    from repro.launch import fl_round
    from repro.sim import batched, engine
    for mod in (engine, batched, fl_round):
        mp.setattr(mod, "vmapped_client_update", _updater_builder(
            mod.vmapped_client_update, lambda fn: lambda p0, *a: p0))


def client_unchanged(mp):
    """One satellite's answer, its returned model, is altered where it is
    produced: the first client returns the model it started from."""
    from repro.launch import fl_round
    from repro.sim import batched, engine

    def wrap(fn):
        def altered(p0, *a):
            out = fn(p0, *a)
            return jax.tree.map(lambda o, s: o.at[0].set(s[0]), out, p0)
        return altered
    for mod in (engine, batched, fl_round):
        mp.setattr(mod, "vmapped_client_update", _updater_builder(
            mod.vmapped_client_update, wrap))


def _drop_odd(w, first: int = 0):
    """Weights with every other client's zeroed: of clients `first`,
    `first + 1`, ... along the last axis, the odd ones are left out."""
    idx = first + jnp.arange(w.shape[-1])
    return jnp.where(idx % 2 == 1, jnp.zeros_like(w), w)


def half_clients(mp):
    """Half of the batch left out: every other client's update gets no
    weight, so the update is the mean over the rest."""
    from repro.core.strategies import base, fedbuff
    from repro.launch import fl_round
    from repro.sim import batched

    avg = base.weighted_average
    mp.setattr(base, "weighted_average",
               lambda stacked, w, **k: avg(stacked, _drop_odd(w), **k))
    delta = fedbuff.weighted_delta_update
    mp.setattr(fedbuff, "weighted_delta_update",
               lambda g, s, w, st, **k: delta(g, s, _drop_odd(w), st, **k))
    vdelta = batched.weighted_delta_update
    mp.setattr(batched, "weighted_delta_update",
               lambda g, s, w, st, lr: vdelta(g, s, _drop_odd(w), st, lr))
    allreduce = fl_round.masked_delta_allreduce

    def mesh_half(g, s, w, axis, **k):
        first = jax.lax.axis_index(axis) * w.shape[-1]
        return allreduce(g, s, _drop_odd(w, first), axis, **k)
    mp.setattr(fl_round, "masked_delta_allreduce", mesh_half)


def exchange_left_out(mp):
    """The exchange between chips left out: the mesh step's server update
    takes the first chip's clients alone instead of the sum over chips."""
    from repro.launch import fl_round

    def local_only(global_params, stacked, weights, axis_name,
                   server_lr=1.0):
        weights = jnp.asarray(weights, jnp.float32)
        scale = weights / jnp.maximum(jnp.sum(weights), 1e-12)

        def leaf(gl, xs):
            wb = scale.reshape((-1,) + (1,) * gl.ndim).astype(gl.dtype)
            part = jnp.sum(wb * (xs - gl[None]), axis=0)
            mine = jax.lax.axis_index(axis_name) == 0
            first = jax.lax.psum(jnp.where(mine, part, 0.0), axis_name)
            return gl + jnp.asarray(server_lr, gl.dtype) * first
        return jax.tree.map(leaf, global_params, stacked)
    mp.setattr(fl_round, "masked_delta_allreduce", local_only)


FAULTS = {"state_unchanged": state_unchanged,
          "client_unchanged": client_unchanged,
          "half_clients": half_clients}
MESH_FAULTS = dict(FAULTS, exchange_left_out=exchange_left_out)
