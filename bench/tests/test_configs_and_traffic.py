"""Configurations' FLOP counts, the plain models, and the traffic
generators."""
import hashlib
import importlib
import inspect
import json
import os

import numpy as np
import pytest

from bench import harness
from bench.cell import client_data
from bench.traffic import femnist, tokens

CONFIGS = os.path.join(harness.BENCH, "configs")
TRAFFIC = os.path.join(harness.BENCH, "traffic")
# sha256 over (key, bytes) of femnist.generate(4, 2**31 + 17) under the
# FEMNIST mixes' data block: the arrays every FEMNIST cell has read.
FEMNIST_DIGEST = ("871715a96c33f3db4e8010fad0d7a85827352963da0127d26ef0e972"
                  "fba8a899")
TOKEN_CFG = {"vocab_size": 512, "seq_len": 32}
TOKEN_DATA = {"median_rows": 16, "shard_sigma": 1.15, "min_rows": 2,
              "max_rows": 128, "eval_rows": 4, "doc_median": 40,
              "doc_sigma": 1.0, "zipf_a": 1.0}


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
    cfg, _ = _config("femnist_mlp")
    a = femnist.generate(4, 2 ** 31 + 17, cfg)
    b = femnist.generate(4, 2 ** 31 + 17, cfg)
    c = femnist.generate(4, 2 ** 31 + 18, cfg)
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    assert not np.array_equal(a["x"], c["x"])


def _digest(out: dict) -> str:
    h = hashlib.sha256()
    for key in sorted(out):
        h.update(key.encode())
        h.update(out[key].tobytes())
    return h.hexdigest()


def test_femnist_arrays_unchanged_through_the_dispatch():
    cfg, _ = _config("femnist_mlp")
    with open(os.path.join(TRAFFIC, "c10s10-g13-fedbuff.json")) as f:
        mix = json.load(f)
    assert cfg["generator"] == "femnist"
    out = client_data(cfg, mix, 4, 2 ** 31 + 17)
    direct = femnist.generate(4, 2 ** 31 + 17, cfg, **mix["data"])
    for key in direct:
        np.testing.assert_array_equal(out[key], direct[key])
    assert _digest(out) == FEMNIST_DIGEST


def test_token_generator_is_deterministic_per_seed():
    a = tokens.generate(5, 2 ** 31 + 17, TOKEN_CFG, **TOKEN_DATA)
    b = tokens.generate(5, 2 ** 31 + 17, TOKEN_CFG, **TOKEN_DATA)
    c = tokens.generate(5, 2 ** 31 + 18, TOKEN_CFG, **TOKEN_DATA)
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    assert not np.array_equal(a["x"], c["x"])


def test_token_rows_ids_and_documents():
    out = tokens.generate(8, 2 ** 31 + 5, TOKEN_CFG, **TOKEN_DATA)
    width = TOKEN_CFG["seq_len"] + 1
    assert out["x"].shape == (8, TOKEN_DATA["max_rows"], width)
    assert out["x_eval"].shape == (8, TOKEN_DATA["eval_rows"], width)
    assert out["x"].dtype == np.int32 and not out["y"].any()
    assert 0 <= out["x"].min() and out["x"].max() < TOKEN_CFG["vocab_size"]
    gaps = []
    for k in range(8):
        nk = out["n"][k]
        assert not out["x"][k, nk:].any()          # rows past n_k: padding
        for rows in (out["x"][k, :nk], out["x_eval"][k]):
            ends = np.flatnonzero(rows.reshape(-1) == tokens.EOS)
            gaps.extend(np.diff(ends) - 1)
    assert len(gaps) > 100 and min(gaps) >= 1       # no empty document
    # Documents: lognormal lengths about their median, with a long tail.
    med = np.median(gaps)
    assert 0.5 * TOKEN_DATA["doc_median"] <= med <= 2 * TOKEN_DATA[
        "doc_median"]
    assert max(gaps) > 4 * med
    # Zipf ids: id 1 is about ten times as frequent as id 10.
    counts = np.bincount(out["x"][out["x"] != tokens.EOS],
                         minlength=TOKEN_CFG["vocab_size"])
    assert 5 * counts[10] < counts[1] < 20 * counts[10]


def test_token_shard_sizes_are_skewed():
    n = tokens.generate(100, 2 ** 31 + 9, TOKEN_CFG, **TOKEN_DATA)["n"]
    assert ((n >= TOKEN_DATA["min_rows"])
            & (n <= TOKEN_DATA["max_rows"])).all()
    assert n.max() / np.median(n) > 4


def _bench_configs() -> list[dict]:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["configs"]
    out = []
    for entry in entries:
        with open(os.path.join(harness.ROOT, entry["file"])) as f:
            out.append(json.load(f))
    return out


@pytest.mark.parametrize("mix", sorted(
    f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json")))
def test_mix_shapes_and_sample_ranges(mix):
    """The mix's data block, through the generator of each configuration
    that takes it, gives shards of the common layout."""
    with open(os.path.join(TRAFFIC, mix + ".json")) as f:
        spec = json.load(f)
    d, K = spec["data"], 6
    made = 0
    for cfg in _bench_configs():
        gen = importlib.import_module("bench.traffic." + cfg["generator"])
        try:
            inspect.signature(gen.generate).bind(K, 7, cfg, **d)
        except TypeError:
            continue                  # a data block of another generator
        out = client_data(cfg, spec, K, 7)
        made += 1
        N, E = out["x"].shape[1], out["x_eval"].shape[1]
        assert out["x"].shape[0] == out["x_eval"].shape[0] == K
        assert out["x_eval"].shape[2:] == out["x"].shape[2:]
        assert out["y"].shape == (K, N) and out["y_eval"].shape == (K, E)
        assert ((out["n"] >= 1) & (out["n"] <= N)).all()
        assert (out["n_eval"] == E).all()
        for k in range(K):   # rows past n_k are padding
            assert not out["x"][k, out["n"][k]:].any()
        if gen is femnist:
            assert out["x"].shape[1:] == (d["max_samples"], 28, 28, 1)
            assert out["x_eval"].shape == (K, d["eval_samples"], 28, 28, 1)
            assert out["x"].dtype == np.float32
            assert ((out["n"] >= d["min_samples"])
                    & (out["n"] <= d["max_samples"])).all()
            assert out["y"].max() < femnist.N_CLASSES
            assert out["y"].min() >= 0
            assert 0.0 <= out["x"].min() and out["x"].max() <= 1.0
        if gen is tokens:
            assert out["x"].shape[1:] == (d["max_rows"], cfg["seq_len"] + 1)
            assert out["x_eval"].shape == (K, d["eval_rows"],
                                           cfg["seq_len"] + 1)
            assert 0 <= out["x"].min()
            assert out["x"].max() < cfg["vocab_size"]
    assert made, f"no configuration takes mix {mix}'s data block"
    assert spec["executor"] in ("host", "mesh", "batched")
    assert spec["check_rounds"] <= spec["rounds"]
