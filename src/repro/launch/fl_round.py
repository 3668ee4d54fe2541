"""FL round over the production mesh — the paper's technique, mesh-native.

The satellite mapping of DESIGN.md section 3: the "pod" axis carries one
orbital cluster per pod; a round's aggregation (Eq. 1) is a *masked*
weighted psum across that axis — satellites with no ground contact this
round contribute zero weight, which is exactly FedBuff's buffer semantics
expressed as a dense ICI collective instead of point-to-point sends.

Two builders, one collective:

  * `make_fl_round_step` — the launch-style contract: a dict batch
    (sharded over the pod axis) and one SGD stream per pod. Generalized
    beyond `ModelConfig`/`lm_loss`: any `loss_fn(params, batch)` works,
    local steps may vary per pod (masked inside a shared fori_loop), and
    weights follow FedBuff semantics (staleness discount + server lr) so
    sync rounds and buffer flushes are the same collective.
  * `make_mesh_round_step` — the simulator's contract: each participating
    satellite is one pod slot carrying its own (x, y, n_valid) shard,
    step budget, aggregation weight, staleness, and RNG — exactly the
    arguments of the vmapped host ClientUpdate, so
    `ConstellationSim(..., execution="mesh")` matches the host path
    client for client. Each mesh shard vmaps its local *block* of pods
    (`repro.core.client.vmapped_client_update`), then
    `masked_delta_allreduce` folds every block into the global model with
    one psum pair — this is what lets an n-pod round run on any host
    backend whose device count is smaller than n.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.aggregation import (
    masked_delta_allreduce,
    participation_masked_psum,
    staleness_discount,
)
from repro.core.client import vmapped_client_update


def _pod_axis(mesh) -> str:
    return "pod" if "pod" in mesh.axis_names else "data"


def make_fl_round_step(cfg=None, mesh=None, lr: float = 1e-3,
                       local_steps: int = 1, prox_mu: float = 0.0, *,
                       loss_fn=None, workload=None, server_lr: float = 1.0,
                       batch_dims: dict[str, int] | None = None):
    """One federated round: every pod runs up to `local_steps` of
    (proximal) SGD on its own shard of the batch, then the global model
    updates with the participation-masked weighted average of the pod
    deltas.

    The loss comes from one of three sources:
      * `cfg` — the original LM contract: a `ModelConfig` driving
        `lm_loss` over a `{"tokens": ...}` batch;
      * `loss_fn(params, batch) -> scalar` — fully generic dict-batch;
      * `workload` — a `repro.core.workload.Workload`: its
        `mesh_batch_dims` declare the dict-batch schema (first key feeds
        the loss's sample stream, an optional "labels" key its targets;
        classification workloads default to {"x": ..., "labels": 1}),
        and its `loss_fn(params, xb, yb)` supplies the math.
    `batch_dims` maps extra batch keys to their array rank (leading dim
    sharded over the pod axis) when the defaults don't cover them.

    Returns ``fn(params, batch, weights, steps=None, staleness=None)``:
      * ``weights`` is (n_pods,) — n_k for participating clusters, 0 for
        out-of-contact ones;
      * ``steps`` (n_pods,) int caps each pod's live SGD steps (default:
        everyone runs `local_steps` — the original fixed-epoch contract);
      * ``staleness`` (n_pods,) int applies FedBuff's 1/sqrt(1+tau)
        discount (with `server_lr`, an async buffer flush is the same
        collective as a sync round).
    """
    axis = _pod_axis(mesh)

    if loss_fn is None and workload is not None:
        wl_dims = dict(workload.mesh_batch_dims or
                       {"x": 1 + len(workload.sample_shape), "labels": 1})
        batch_dims = {**wl_dims, **(batch_dims or {})}
        x_key = next(iter(wl_dims))
        wl_loss = workload.loss_fn

        def loss_fn(params, batch):
            return wl_loss(params, batch[x_key], batch.get("labels"))

    if loss_fn is None:
        if cfg is None:
            raise ValueError(
                "make_fl_round_step needs cfg, loss_fn, or workload")
        from repro.train.step import lm_loss
        loss_fn = lambda p, b: lm_loss(cfg, p, b)[0]          # noqa: E731

    grad_fn = jax.grad(loss_fn)

    def pod_round(params, batch, weight, steps, staleness):
        # Inside shard_map over `axis`: batch is this pod's shard, weight
        # is this pod's scalar participation weight.
        w = weight[0] * staleness_discount(staleness[0])
        # The replicated params seed a carry that comes back varying over
        # the pod axis (each pod trains on its own shard).
        local = jax.lax.pcast(params, (axis,), to="varying")

        def body(i, local):
            g = grad_fn(local, batch)
            live = (i < steps[0]).astype(jnp.float32)
            return jax.tree.map(
                lambda p, gi, p0: p - lr * live * (gi + prox_mu * (p - p0)),
                local, g, params)

        local = jax.lax.fori_loop(0, local_steps, body, local)
        delta = jax.tree.map(lambda a, b: a - b, local, params)
        agg = participation_masked_psum(delta, w, axis)
        return jax.tree.map(
            lambda p, d: p + jnp.asarray(server_lr, p.dtype) * d,
            params, agg)

    n_batch_dims = {"tokens": 2, "prefix_embeds": 3, "enc_embeds": 3}
    if batch_dims:
        n_batch_dims = {**n_batch_dims, **batch_dims}
    batch_specs = {
        k: P(axis, *([None] * (n - 1))) for k, n in n_batch_dims.items()}

    def round_step(params, batch, weights, steps=None, staleness=None):
        n_pods = weights.shape[0]
        if steps is None:
            steps = jnp.full((n_pods,), local_steps, jnp.int32)
        if staleness is None:
            staleness = jnp.zeros((n_pods,), jnp.int32)
        specs = {k: batch_specs[k] for k in batch}
        return jax.shard_map(
            pod_round,
            mesh=mesh,
            in_specs=(P(), specs, P(axis), P(axis), P(axis)),
            out_specs=P(),
            axis_names={axis},
        )(params, batch, weights, steps, staleness)

    return round_step


def make_mesh_round_step(loss_fn, mesh, *, lr: float, batch_size: int,
                         max_steps: int, server_lr: float = 1.0,
                         axis: str | None = None, codec=None):
    """Mesh-native ClientUpdate + aggregation with the simulator contract.

    Returns ``fn(global_params, anchors, x, y, n_valid, steps, weights,
    staleness, prox_mu, rngs) -> new_global_params`` where every argument
    except `global_params`/`prox_mu` carries a leading pod axis whose
    length must be a multiple of the mesh's pod-axis size (pad surplus
    slots with weight 0 and steps 0 — they contribute nothing, exactly
    like an out-of-contact satellite).

    `anchors` is the stacked per-pod proximal anchor (the round's global
    model broadcast for the sync barrier; per-client historical versions
    for FedBuff) and doubles as each pod's initial parameters, mirroring
    `ConstellationSim._run_clients`.

    `codec` (a lossy `repro.comms.codec.TransferCodec`, or None) replays
    each pod's uplink on the wire: the aggregation sees anchor +
    codec.apply(delta) instead of the raw client return — same per-pod
    RNG stream as the updater, so the host path decodes identically.
    """
    axis = axis or _pod_axis(mesh)
    vcu = vmapped_client_update(loss_fn, lr=lr, batch_size=batch_size,
                                max_steps=max_steps, anchored=True)
    rt = None
    if codec is not None and codec.lossy:
        from repro.comms.codec import client_roundtrip
        rt = jax.vmap(client_roundtrip(codec), in_axes=(0, 0, 0))

    def shard_body(global_params, anchors, x, y, n, steps, weights,
                   staleness, prox_mu, rngs):
        # Local shapes: every per-pod argument holds this shard's block of
        # pods; the client math is the same vmapped function the host
        # path jits, so the two execution modes agree client for client.
        client_params = vcu(anchors, anchors, x, y, n, steps, prox_mu, rngs)
        if rt is not None:
            client_params = rt(client_params, anchors, rngs)
        w = weights * staleness_discount(staleness)
        return masked_delta_allreduce(global_params, client_params, w,
                                      axis, server_lr=server_lr)

    def round_step(global_params, anchors, x, y, n, steps, weights,
                   staleness, prox_mu, rngs):
        return jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis),
                      P(axis), P(axis), P(), P(axis)),
            out_specs=P(),
            axis_names={axis},
        )(global_params, anchors, x, y, n, steps, weights, staleness,
          prox_mu, rngs)

    return round_step
