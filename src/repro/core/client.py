"""ClientUpdate — the on-board local training step (paper Algorithms 1-3).

One jitted, vmap-able function covers all three strategies:

  * FedAvg:  prox_mu = 0, epochs = E (same for everyone);
  * FedProx / FedBuff: prox_mu > 0, per-client epoch counts coming from the
    orbital itinerary (train-until-contact), realised by masking steps
    beyond a client's budget inside a shared fori_loop.

The update is *workload-agnostic*: the data term is any
``loss_fn(params, xb, yb) -> scalar`` (classification cross-entropy,
LM next-token CE, ...); this module only adds the proximal term
``0.5 * mu * ||w - w_anchor||^2`` and the masked SGD loop around it.
Passing ``apply_fn`` instead keeps the seed's FEMNIST contract
(cross-entropy over logits) bit for bit.

The proximal gradient  g + mu * (w - w_anchor)  and the SGD update are the
fused-update hot spot the Pallas `prox_sgd` kernel implements; the jnp path
here is the oracle.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def classification_loss(apply_fn: Callable) -> Callable:
    """The seed's FEMNIST data term: mean cross-entropy over logits."""

    def loss_fn(params, xb, yb):
        return jnp.mean(cross_entropy(apply_fn(params, xb), yb))

    return loss_fn


def make_client_update(
    apply_fn: Callable | None = None,
    lr: float = 0.05,
    batch_size: int = 32,
    max_steps: int = 64,
    *,
    loss_fn: Callable | None = None,
    has_aux: bool = False,
    unroll: bool = False,
) -> Callable:
    """Build the jitted ClientUpdate.

    Provide either `apply_fn` (classification: cross-entropy over logits,
    the seed contract) or a generic `loss_fn(params, xb, yb) -> scalar`
    data term (any workload: LM next-token CE, regression, ...).

    Returns fn(params0, anchor, x, y, n_valid, steps, prox_mu, rng) -> params
    where every array may carry a leading client axis under vmap:
      x: (N, *sample_shape), y: (N,), n_valid: () int, steps: () int
      <= max_steps.
    `anchor` is the round's global model (the proximal anchor w_t).

    With `has_aux`, `loss_fn` returns (loss, stats), a dict of arrays the
    step counts (routing statistics, say), and the carried state is
    (params, stats): `params0` becomes (params0, stats0) and so does the
    return, each live step adding its stats (`zero_stats` gives stats0).

    `unroll` writes the `max_steps` steps out in the program instead of a
    loop, for a model whose copies fill the device: there XLA updates the
    parameters in place from step to step, where a loop keeps both ends
    of its carry (a few steps only: the program grows with each).
    """
    if loss_fn is None:
        if apply_fn is None:
            raise ValueError("make_client_update needs apply_fn or loss_fn")
        loss_fn = classification_loss(apply_fn)

    def prox_loss_fn(params, anchor, x, y, prox_mu):
        data, stats = loss_fn(params, x, y) if has_aux else (
            loss_fn(params, x, y), None)
        sq = sum(jnp.sum((p - a) ** 2)
                 for p, a in zip(jax.tree.leaves(params),
                                 jax.tree.leaves(anchor)))
        total = data + 0.5 * prox_mu * sq
        return (total, stats) if has_aux else total

    grad_fn = jax.grad(prox_loss_fn, has_aux=has_aux)

    def client_update(params0, anchor, x, y, n_valid, steps, prox_mu, rng):
        def body(i, carry):
            state, rng = carry
            params = state[0] if has_aux else state
            rng, sub = jax.random.split(rng)
            idx = jax.random.randint(sub, (batch_size,), 0, jnp.maximum(n_valid, 1))
            out = grad_fn(params, anchor, x[idx], y[idx], prox_mu)
            g = out[0] if has_aux else out
            live = (i < steps).astype(jnp.float32)
            params = jax.tree.map(lambda p, gi: p - lr * live * gi, params, g)
            if has_aux:
                params = (params, jax.tree.map(
                    lambda a, b: a + (i < steps).astype(b.dtype) * b,
                    state[1], out[1]))
            return params, rng

        params, _ = jax.lax.fori_loop(0, max_steps, body, (params0, rng),
                                      unroll=unroll)
        return params

    return client_update


def zero_stats(loss_fn: Callable, params, x, y, batch_size: int):
    """Zeros shaped like the stats `loss_fn` returns for one minibatch of
    one client's shard `x`, `y` (the `has_aux` client state's start)."""
    shapes = jax.eval_shape(loss_fn, params, x[:batch_size], y[:batch_size])
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes[1])


def vmapped_client_update(loss_fn: Callable, *, lr: float = 0.05,
                          batch_size: int = 32, max_steps: int = 64,
                          anchored: bool = False,
                          has_aux: bool = False) -> Callable:
    """vmap ClientUpdate over a stacked client axis (not jitted).

    The one builder behind both execution paths: `sim.engine` jits it for
    the vmapped host loop, `launch.fl_round` closes over it inside a
    shard_map body (each mesh shard vmaps its local block of clients), so
    the per-client math is the same function object in either mode.

    `anchored=False` broadcasts one shared anchor (the sync barrier);
    `anchored=True` maps per-client anchors (FedBuff historical versions).
    `has_aux` as in `make_client_update`.
    """
    cu = make_client_update(loss_fn=loss_fn, lr=lr, batch_size=batch_size,
                            max_steps=max_steps, has_aux=has_aux)
    axes = (0, 0 if anchored else None, 0, 0, 0, 0, None, 0)
    return jax.vmap(cu, in_axes=axes)


def make_batched_client_update(apply_fn, lr=0.05, batch_size=32, max_steps=64):
    """Seed-contract convenience: jitted vmapped ClientUpdate for an
    image-classifier (init, apply) pair — `vmapped_client_update` with
    the cross-entropy data term."""
    return jax.jit(vmapped_client_update(
        classification_loss(apply_fn), lr=lr, batch_size=batch_size,
        max_steps=max_steps))


@functools.partial(jax.jit, static_argnames=("apply_fn",))
def evaluate(apply_fn, params, x, y, n_valid):
    """Weighted accuracy over stacked eval clients.

    x: (K, N, ...), y: (K, N), n_valid: (K,). Returns scalar accuracy.
    """
    def one(xk, yk):
        logits = apply_fn(params, xk)
        return (jnp.argmax(logits, -1) == yk).astype(jnp.float32)
    correct = jax.vmap(one)(x, y)                       # (K, N)
    mask = (jnp.arange(x.shape[1])[None, :] < n_valid[:, None]).astype(jnp.float32)
    return jnp.sum(correct * mask) / jnp.maximum(jnp.sum(mask), 1.0)
