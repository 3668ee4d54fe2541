"""Server-side aggregation math (paper Eq. 1), pure JAX.

`weighted_average` is the hot spot of every FL round: a weighted reduction
over K stacked client models. Two execution paths:

  * `jnp` einsum (default, differentiable, runs anywhere);
  * the Pallas `fedagg` kernel (`repro.kernels.fedagg`) for the flattened
    fast path on TPU — selected via `use_kernel=True` or the
    `REPRO_FEDAGG_KERNEL=1` env var.

Both paths are oracle-checked against each other in tests.
"""
from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any


def normalized_weights(weights: jax.Array) -> jax.Array:
    """n_k / m_t with a zero-sum guard (empty rounds keep the old model)."""
    weights = jnp.asarray(weights, jnp.float32)
    total = jnp.sum(weights)
    return jnp.where(total > 0, weights / jnp.maximum(total, 1e-12), weights)


def weighted_average(stacked: Pytree, weights: jax.Array,
                     use_kernel: bool | None = None) -> Pytree:
    """w <- sum_k (n_k / m) w_k over the leading (client) axis of each leaf."""
    w = normalized_weights(weights)
    if use_kernel is None:
        use_kernel = os.environ.get("REPRO_FEDAGG_KERNEL", "0") == "1"
    if use_kernel:
        from repro.kernels.ops import fedagg_pytree
        return fedagg_pytree(stacked, w)
    return fold_weighted(None, stacked, w)


def fold_weighted(acc: Pytree | None, stacked: Pytree,
                  w: jax.Array) -> Pytree:
    """acc + sum_k w_k x_k over the leading (client) axis of each leaf of
    `stacked`; `acc` None starts the sum. Folding a round's clients group
    by group under its `normalized_weights` gives Eq. 1's average."""
    def leaf(x):
        wb = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return jnp.sum(wb * x, axis=0)
    total = jax.tree.map(leaf, stacked)
    return total if acc is None else jax.tree.map(jnp.add, acc, total)


def staleness_discount(staleness: jax.Array) -> jax.Array:
    """FedBuff's staleness discount s(tau) = 1/sqrt(1+tau).

    Shared by the host event loop (`weighted_delta_update`) and the mesh
    round step (`repro.launch.fl_round`), so both execution paths apply
    identical weight semantics to buffered updates.
    """
    return 1.0 / jnp.sqrt(1.0 + jnp.asarray(staleness, jnp.float32))


def admission_weights(ns, staleness, max_staleness: int):
    """FedBuff admission rule: updates staler than the bound get zero
    weight. Works on numpy or jax arrays (`ns` are raw sample counts)."""
    admit = staleness <= max_staleness
    return ns * admit


def weighted_delta_update(global_params: Pytree, stacked: Pytree,
                          weights: jax.Array, staleness: jax.Array,
                          server_lr: float = 1.0) -> Pytree:
    """Buffered-async update (FedBuff):

        w <- w + lr_g * sum_k s(tau_k) * (n_k/m) * (w_k - w)

    with the staleness discount s(tau) = 1/sqrt(1+tau) of the FedBuff paper.
    Weights of inadmissible (over-stale) clients must already be zeroed.
    """
    disc = staleness_discount(staleness)
    w = normalized_weights(jnp.asarray(weights, jnp.float32) * disc)

    def leaf(gl, xs):
        wb = w.reshape((-1,) + (1,) * gl.ndim).astype(gl.dtype)
        delta = jnp.sum(wb * (xs - gl[None]), axis=0)
        return gl + jnp.asarray(server_lr, gl.dtype) * delta

    return jax.tree.map(leaf, global_params, stacked)


def masked_delta_allreduce(global_params: Pytree, stacked: Pytree,
                           weights: jax.Array, axis_name: str,
                           server_lr: float = 1.0) -> Pytree:
    """Mesh-native form of the server update, for shard_map bodies whose
    shards each hold a *block* of clients (leading local axis on every
    `stacked` leaf; `weights` is the matching local (P_local,) block).

        w <- w + lr_g * sum_k (w_k / sum_j w_j) * (p_k - w)

    The weight total is psummed over `axis_name`, so masking (weight 0)
    and the empty-round guard (total 0 keeps the old model) are global
    across the mesh. With lr_g=1 and weights summing over participants
    this equals `weighted_average(stacked, weights)` (Eq. 1); with
    discounted weights and lr_g it equals `weighted_delta_update` —
    one collective covers the sync barrier and FedBuff flushes.
    """
    weights = jnp.asarray(weights, jnp.float32)
    total = jax.lax.psum(jnp.sum(weights), axis_name)
    scale = jnp.where(total > 0, weights / jnp.maximum(total, 1e-12), 0.0)

    def leaf(gl, xs):
        wb = scale.reshape((-1,) + (1,) * gl.ndim).astype(gl.dtype)
        part = jnp.sum(wb * (xs - gl[None]), axis=0)
        delta = jax.lax.psum(part, axis_name)
        return gl + jnp.asarray(server_lr, gl.dtype) * delta

    return jax.tree.map(leaf, global_params, stacked)


def participation_masked_psum(update: Pytree, weight: jax.Array,
                              axis_name: str) -> Pytree:
    """Mesh-native FL aggregation (TPU adaptation, DESIGN.md section 3).

    Each mesh shard along `axis_name` is one satellite client; `weight` is
    n_k for participants and 0 for satellites with no ground contact this
    round. The paper's "round completion" barrier becomes a dense masked
    all-reduce — the ICI-native equivalent of gathering returned models.
    Intended to run inside shard_map.
    """
    total = jax.lax.psum(weight, axis_name)
    scale = jnp.where(total > 0, weight / jnp.maximum(total, 1e-12), 0.0)
    return jax.tree.map(
        lambda x: jax.lax.psum(x * scale.astype(x.dtype), axis_name), update)
