"""Configurations' FLOP counts, the plain models, and the traffic
generator."""
import json
import os

import numpy as np
import pytest

from bench import harness
from bench.traffic import femnist

CONFIGS = os.path.join(harness.BENCH, "configs")
TRAFFIC = os.path.join(harness.BENCH, "traffic")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    return cfg, harness._module(os.path.join(CONFIGS, name + ".py"),
                                "test_cfg_" + name)


@pytest.mark.parametrize("name,flops,params", [
    ("femnist_mlp", 93_072, 46_639),       # 2 x (784*56 + 56*47)
    ("femnist_cnn", 657_552, 47_887),      # 2 x 328,776 MACs
])
def test_forward_flops_and_params_match_hand_counts(name, flops, params):
    import jax
    cfg, model = _config(name)
    assert model.forward_flops(cfg) == flops
    tree = model.init(cfg, jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == params
    assert cfg["n_params"] == params
    logits = model.apply(tree, np.zeros((3, 28, 28, 1), np.float32))
    assert logits.shape == (3, cfg["classes"])


def test_generator_is_deterministic_per_seed():
    a = femnist.generate(4, seed=2 ** 31 + 17)
    b = femnist.generate(4, seed=2 ** 31 + 17)
    c = femnist.generate(4, seed=2 ** 31 + 18)
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    assert not np.array_equal(a["x"], c["x"])


@pytest.mark.parametrize("mix", sorted(
    f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json")))
def test_mix_shapes_and_sample_ranges(mix):
    with open(os.path.join(TRAFFIC, mix + ".json")) as f:
        spec = json.load(f)
    d = spec["data"]
    out = femnist.generate(6, seed=7, **d)
    K, N = 6, d["max_samples"]
    assert out["x"].shape == (K, N, 28, 28, 1)
    assert out["x_eval"].shape == (K, d["eval_samples"], 28, 28, 1)
    assert out["x"].dtype == np.float32
    assert ((out["n"] >= d["min_samples"])
            & (out["n"] <= d["max_samples"])).all()
    assert (out["n_eval"] == d["eval_samples"]).all()
    assert out["y"].max() < femnist.N_CLASSES and out["y"].min() >= 0
    assert 0.0 <= out["x"].min() and out["x"].max() <= 1.0
    for k in range(K):   # rows past n_k are padding
        assert not out["x"][k, out["n"][k]:].any()
    assert spec["executor"] in ("host", "mesh", "batched")
    assert spec["check_rounds"] <= spec["rounds"]
