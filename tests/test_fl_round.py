"""Mesh-native FL round: masked psum aggregation semantics on a host mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.fl_round import make_fl_round_step
from repro.models.lm import init_params


@pytest.fixture(scope="module")
def setup():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("gemma-2b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)}
    return mesh, cfg, params, batch


def test_participating_round_moves_params(setup):
    mesh, cfg, params, batch = setup
    step = make_fl_round_step(cfg, mesh, lr=1e-2)
    with mesh:
        out = step(params, batch, jnp.asarray([300.0]))
    delta = sum(float(jnp.abs(a - b).sum())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(out)))
    assert delta > 0.0


def test_masked_round_is_identity(setup):
    """Zero participation weight (no ground contact) keeps the old model —
    the paper's round-completion rule as a dense collective."""
    mesh, cfg, params, batch = setup
    step = make_fl_round_step(cfg, mesh, lr=1e-2)
    with mesh:
        out = step(params, batch, jnp.asarray([0.0]))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_variable_local_steps_mask(setup):
    """steps=0 freezes a pod even when its weight participates — the
    generalized round step's variable-local-work contract."""
    mesh, cfg, params, batch = setup
    step = make_fl_round_step(cfg, mesh, lr=1e-2, local_steps=4)
    with mesh:
        out = step(params, batch, jnp.asarray([300.0]),
                   steps=jnp.asarray([0], jnp.int32))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_generic_loss_fn_replaces_model_config(setup):
    """The launch surface is workload-generic: any loss_fn(params, batch)
    drives the same collective (here: a quadratic toy objective)."""
    mesh, _, _, _ = setup

    def loss_fn(params, batch):
        del batch
        return jnp.sum(params["w"] ** 2)

    step = make_fl_round_step(mesh=mesh, lr=0.5, local_steps=1,
                              loss_fn=loss_fn,
                              batch_dims={"obs": 2})
    params = {"w": jnp.asarray([2.0, -4.0])}
    batch = {"obs": jnp.zeros((1, 1))}
    with mesh:
        out = step(params, batch, jnp.asarray([1.0]))
    # One SGD step on sum(w^2): w <- w - lr * 2w = 0 at lr=0.5.
    np.testing.assert_allclose(np.asarray(out["w"]), [0.0, 0.0], atol=1e-6)


def test_fedbuff_weight_semantics_on_mesh(setup):
    """Staleness discounting + server_lr are collective-native: a stale
    pod's delta shrinks by 1/sqrt(1+tau) x server_lr relative to the
    fresh run (single pod, so normalization cancels and the discount
    shows up only through server_lr scaling of the same delta)."""
    mesh, _, _, _ = setup

    def loss_fn(params, batch):
        del batch
        return jnp.sum(params["w"])          # constant gradient of 1

    params = {"w": jnp.asarray([0.0, 0.0])}
    batch = {"obs": jnp.zeros((1, 1))}
    kw = dict(mesh=mesh, lr=1.0, local_steps=1, loss_fn=loss_fn,
              batch_dims={"obs": 2})
    fresh = make_fl_round_step(**kw)
    halved = make_fl_round_step(server_lr=0.5, **kw)
    with mesh:
        out_f = fresh(params, batch, jnp.asarray([10.0]))
        out_h = halved(params, batch, jnp.asarray([10.0]),
                       staleness=jnp.asarray([3], jnp.int32))
    # Fresh: w - lr*1 = -1. server_lr=0.5 halves the aggregated delta;
    # with one pod the staleness discount normalizes away (FedBuff's
    # per-update discount is relative within the buffer).
    np.testing.assert_allclose(np.asarray(out_f["w"]), [-1.0, -1.0],
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_h["w"]), [-0.5, -0.5],
                               atol=1e-6)


def test_workload_batch_specs_drive_round_step():
    """A Workload's `mesh_batch_dims` declare the launch-surface batch
    schema: make_fl_round_step(workload=...) builds the dict-batch loss
    from the workload's own (loss_fn, batch spec) pair — for the LM
    contract ({"tokens": ...}) and the classification default
    ({"x": ..., "labels": ...})."""
    from repro.core import get_workload

    mesh = jax.make_mesh((1, 1), ("data", "model"))

    lm = get_workload("lm_tiny")
    assert lm.mesh_batch_dims == {"tokens": 2}
    step = make_fl_round_step(mesh=mesh, lr=1e-2, workload=lm)
    params = lm.init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 128, (2, 33)), jnp.int32)}
    with mesh:
        out = step(params, batch, jnp.asarray([10.0]))
    moved = sum(float(jnp.abs(a - b).sum())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(out)))
    assert moved > 0.0

    mlp = get_workload("femnist_mlp")
    step = make_fl_round_step(mesh=mesh, lr=1e-2, workload=mlp)
    params = mlp.init_fn(jax.random.PRNGKey(1))
    batch = {"x": jnp.asarray(rng.normal(size=(4, 28, 28, 1)), jnp.float32),
             "labels": jnp.asarray(rng.integers(0, 47, (4,)), jnp.int32)}
    with mesh:
        out = step(params, batch, jnp.asarray([10.0]))
    moved = sum(float(jnp.abs(a - b).sum())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(out)))
    assert moved > 0.0


def test_mesh_round_step_matches_vmapped_client_update():
    """`make_mesh_round_step` (the simulator contract) reproduces the
    host path exactly: same vmapped ClientUpdate, then Eq. 1."""
    from repro.core.aggregation import weighted_average
    from repro.core.client import vmapped_client_update
    from repro.launch.fl_round import make_mesh_round_step
    from repro.sharding import client_mesh

    def loss_fn(params, xb, yb):
        pred = xb @ params["w"]
        return jnp.mean((pred - yb) ** 2)

    K, N, D = 3, 16, 4
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(K, N, D)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    n = jnp.full((K,), N, jnp.int32)
    steps = jnp.asarray([4, 2, 0], jnp.int32)
    weights = jnp.asarray([100.0, 50.0, 0.0])
    stale = jnp.zeros((K,), jnp.int32)
    gparams = {"w": jnp.asarray(rng.normal(size=(D,)), jnp.float32)}
    rngs = jax.random.split(jax.random.PRNGKey(7), K)
    anchors = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (K,) + a.shape), gparams)

    # Host oracle: vmapped ClientUpdate + weighted average.
    vcu = vmapped_client_update(loss_fn, lr=0.05, batch_size=8,
                                max_steps=4, anchored=True)
    stacked = vcu(anchors, anchors, x, y, n, steps, 0.1, rngs)
    host = weighted_average(stacked, weights)

    mesh = client_mesh(K)
    step = make_mesh_round_step(loss_fn, mesh, lr=0.05, batch_size=8,
                                max_steps=4)
    out = step(gparams, anchors, x, y, n, steps, weights, stale, 0.1, rngs)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(host["w"]), atol=1e-6)


def test_fl_round_lowers_on_production_mesh():
    """The FL round step lowers against the 2x16x16 multi-pod mesh specs
    (AbstractMesh: no devices needed)."""
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("gemma-2b").reduced()
    params_s = jax.eval_shape(lambda k: init_params(cfg, k),
                              jax.random.PRNGKey(0))
    batch_s = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    w_s = jax.ShapeDtypeStruct((2,), jnp.float32)
    step = make_fl_round_step(cfg, mesh, lr=1e-2, prox_mu=0.1)
    # Abstract lowering: trace through shard_map without real devices.
    out = jax.eval_shape(step, params_s, batch_s, w_s)
    assert jax.tree.structure(out) == jax.tree.structure(params_s)
