"""Expert-parallel all-to-all MoE: exactness vs the row-local path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.lm.config import MoEConfig
from repro.models.lm.moe import apply_moe, apply_moe_ep, init_moe


@pytest.fixture(scope="module")
def setup():
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=64, n_shared=1,
                    capacity_factor=8.0)
    p = init_moe(jax.random.PRNGKey(0), 32, cfg, "swiglu")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 128, 32)) * 0.5, jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return cfg, p, x, mesh


def test_ep_matches_row_local(setup):
    cfg, p, x, mesh = setup
    y1, a1 = apply_moe(p, x, cfg, "swiglu")
    y2, a2 = apply_moe_ep(p, x, cfg, "swiglu", ("data",), "data", 1, mesh)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(a1["load_balance"]),
                               float(a2["load_balance"]), rtol=1e-6)


def test_ep_differentiable(setup):
    cfg, p, x, mesh = setup

    def loss(p_):
        y, aux = apply_moe_ep(p_, x, cfg, "swiglu", ("data",), "data", 1,
                              mesh)
        return jnp.sum(y ** 2) + aux["load_balance"]

    g = jax.grad(loss)(p)
    total = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
    assert np.isfinite(total) and total > 0


def test_ep_lowers_on_abstract_production_mesh():
    """EP compiles symbolically against a (data=4, model=2) mesh where the
    all_to_all is non-trivial (E=4 experts over 4 shards)."""
    from jax.sharding import AbstractMesh
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=64,
                    capacity_factor=4.0)
    p = jax.eval_shape(lambda k: init_moe(k, 32, cfg, "swiglu"),
                       jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((8, 128, 32), jnp.float32)
    mesh = AbstractMesh((4, 2), ("data", "model"))
    out = jax.eval_shape(
        lambda pp, xx: apply_moe_ep(pp, xx, cfg, "swiglu", ("data",),
                                    "data", 4, mesh), p, x)
    assert out[0].shape == (8, 128, 32)
