"""Sharding-rule unit tests (host mesh; the 512-way mesh is dryrun-only)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models.lm import init_params
from repro.sharding.specs import batch_pspec, param_pspecs


@pytest.fixture(scope="module")
def mesh44():
    # 16 logical devices are not available under pytest (1 CPU device), so
    # rules are exercised against an abstract mesh via AbstractMesh.
    from jax.sharding import AbstractMesh
    return AbstractMesh((4, 4), ("data", "model"))


def test_param_specs_cover_tree(mesh44):
    cfg = get_config("gemma-2b").reduced()
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = param_pspecs(params, mesh44)
    leaves_p = jax.tree.leaves(params)
    leaves_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_p) == len(leaves_s)
    for p, s in zip(leaves_p, leaves_s):
        assert isinstance(s, P)
        assert len(s) <= p.ndim
        # every named axis must divide its dimension
        for dim, ax in zip(p.shape, tuple(s) + (None,) * (p.ndim - len(s))):
            if ax is None:
                continue
            size = np.prod([mesh44.shape[a] for a in
                            (ax if isinstance(ax, tuple) else (ax,))])
            assert dim % size == 0, (p.shape, s)


def test_moe_expert_rules(mesh44):
    cfg = get_config("deepseek-v3-671b")
    import functools
    params = jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0))
    specs = param_pspecs(params, mesh44)
    moe_spec = specs["segments"][1]["moe"]["w1"]
    # stacked layer axis first, then (E, d, ff): E over fsdp, ff over model
    assert moe_spec == P(None, ("data",), None, "model")


def test_batch_pspec_divisibility(mesh44):
    assert batch_pspec(mesh44, 256) == ("data",)
    assert batch_pspec(mesh44, 1) is None
