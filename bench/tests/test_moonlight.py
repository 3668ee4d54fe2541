"""Moonlight-16B-A3B's one-chip share against its plain reference, on the
CPU at small widths with the published structure: a direct-query MLA, a
dense layer then 5 MoE layers, the sigmoid router with its selection
bias, top 6 of 64, gates normalised and scaled, 2 shared experts, 8 of
the 64 experts held, a sliced vocabulary (`moonlight_tiny/`, kept out of
`BENCHMARK.json`).

- The program's loss and gradients are the reference's on seeded random
  weights, drawn alike from one key.
- The share: the 8 shares of 8 experts each, the shared experts counted
  once, add up to the uncut 64-expert reference layer.
- No token is dropped however skewed the routing.
- Through `harness.run_cell`: a sound run is correct, clients streamed
  one at a time too, and each fault of `small.FAULTS` is not.
"""
import copy
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests import small

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "moonlight_tiny")
PLAIN = harness._module(os.path.join(harness.BENCH, "configs",
                                     "moonlight_16b_a3b.py"),
                        "bench_test_moonlight_plain")


def program_config(cfg: dict):
    """The program's ModelConfig of a configuration file shaped like
    `moonlight_16b_a3b.json`: the published model's chip share, at the
    file's widths."""
    from repro.configs import get_config
    from repro.configs.moonlight_16b_a3b import CHIP_SHARE
    from repro.models.lm.config import MLAConfig
    share = get_config("moonlight-16b-a3b").share(**CHIP_SHARE)
    return dataclasses.replace(
        share, d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        moe=dataclasses.replace(share.moe,
                                d_ff_expert=cfg["moe_intermediate_size"]),
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"],
                      kv_lora_rank=cfg["kv_lora_rank"],
                      rope_head_dim=cfg["qk_rope_head_dim"],
                      nope_head_dim=cfg["qk_nope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        remat=True)


def tiny_workload(cfg: dict):
    from repro.core.workload import lm_workload
    return lm_workload(program_config(cfg), name=cfg["workload"],
                       seq_len=cfg["seq_len"], with_routing_stats=True)


@pytest.fixture(scope="module")
def tiny():
    return harness._json(HERE, "moonlight_tiny.json")


def test_config_file_is_the_program_share():
    """The benchmark's configuration file and the program's registered
    workload describe the same model, down to the parameter count."""
    from repro.configs import get_config
    from repro.configs.moonlight_16b_a3b import CHIP_SHARE
    from repro.core.workload import get_workload
    cfg = harness._json(harness.BENCH, "configs", "moonlight_16b_a3b.json")
    share = get_config("moonlight-16b-a3b").share(**CHIP_SHARE)
    assert program_config(cfg) == dataclasses.replace(share, remat=True)
    assert get_workload(cfg["workload"]).n_params == cfg["n_params"]
    shapes = jax.eval_shape(lambda k: PLAIN.init(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == cfg["n_params"]
    dep = cfg["deployment"]
    assert dep["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64,
                                "vocab_size": 163840}
    assert len(dep["held_experts"]) == cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * dep["chips_per_layer"] == 163840


def test_program_matches_reference_loss_and_gradients(tiny):
    wl = tiny_workload(tiny)
    key = jax.random.PRNGKey(3)
    got, ref = wl.init_fn(key), PLAIN.init(tiny, key)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, tiny["seq_len"] + 1),
                           0, tiny["vocab_size"])
    with jax.default_matmul_precision("highest"):
        loss, stats = wl.loss_fn(got, x, None)
        want = PLAIN.data_loss(tiny, ref, x)
        g = jax.grad(lambda p: wl.loss_fn(p, x, None)[0])(got)
        r = jax.grad(lambda p: PLAIN.data_loss(tiny, p, x))(ref)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    flat = jax.tree_util.tree_flatten_with_path
    for (path, a), (_, b) in zip(flat(g)[0], flat(r)[0], strict=True):
        a, b = np.asarray(a), np.asarray(b)
        scale = max(float(np.max(np.abs(b))), 1e-30)
        assert float(np.max(np.abs(a - b))) / scale < 1e-5, \
            jax.tree_util.keystr(path)
    assert int(stats["moe.dropped"]) == 0
    assert 0 < int(stats["moe.local_picks"]) <= 5 * 2 * tiny["seq_len"] * 6


def unwritten_rows_read_nan(monkeypatch):
    """On the TPU the grouped matmul writes only the rows its groups
    cover, forward and backward, and reads no other row; on the CPU it
    writes zeros there. Make those rows NaN here, as the TPU's memory may
    hold anything."""
    orig = jax.lax.ragged_dot

    def live(a, gs):
        return (jnp.arange(a.shape[0]) < jnp.sum(gs))[:, None]

    @jax.custom_vjp
    def ragged(lhs, rhs, gs):
        out = orig(jnp.where(live(lhs, gs), lhs, 0.0), rhs, gs)
        return jnp.where(live(out, gs), out, jnp.nan)

    def fwd(lhs, rhs, gs):
        return ragged(lhs, rhs, gs), (lhs, rhs, gs)

    def bwd(res, g):
        lhs, rhs, gs = res
        _, vjp = jax.vjp(lambda a, b: orig(a, b, gs),
                         jnp.where(live(lhs, gs), lhs, 0.0), rhs)
        dl, dr = vjp(jnp.where(live(g, gs), g, 0.0))
        return jnp.where(live(dl, gs), dl, jnp.nan), dr, None

    ragged.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged)


@pytest.mark.parametrize("unwritten", ["zeros", "nan"])
def test_gradients_ignore_rows_past_the_groups(tiny, unwritten, monkeypatch):
    """The loss and gradients are the reference's whatever the grouped
    matmul leaves in the rows past its groups."""
    if unwritten == "nan":
        unwritten_rows_read_nan(monkeypatch)
    wl = tiny_workload(tiny)
    params = wl.init_fn(jax.random.PRNGKey(4))
    x = jax.random.randint(jax.random.PRNGKey(2), (2, tiny["seq_len"] + 1),
                           0, tiny["vocab_size"])
    with jax.default_matmul_precision("highest"):
        g = jax.grad(lambda p: wl.loss_fn(p, x, None)[0])(params)
        r = jax.grad(lambda p: PLAIN.data_loss(tiny, p, x))(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(r), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def _layer(held, d=32, ff=16, seed=0):
    """An expert layer's config and parameters at small widths: all 64
    experts, or the `held` ones sliced from the same draw."""
    from repro.models.lm.config import MoEConfig
    from repro.models.lm.moe import init_moe
    full = MoEConfig(n_experts=64, top_k=6, d_ff_expert=ff, n_shared=2,
                     scoring="sigmoid", routed_scale=2.446,
                     router_aux_coef=0.001)
    p = init_moe(jax.random.PRNGKey(seed), d, full, "swiglu")
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                               (64,))
    if held is None:
        return full, p
    idx = np.asarray(held)
    part = dict(p, **{n: p[n][idx] for n in ("w1", "w2", "w3")})
    return dataclasses.replace(full, held=tuple(held)), part


def _plain_layer(p, x, held, ff):
    cfg = copy.deepcopy(PLAIN.CFG)
    cfg["deployment"]["held_experts"] = list(held)
    cfg["moe_intermediate_size"] = ff
    return PLAIN._experts(p, x, cfg)


def test_shares_add_up_to_the_uncut_layer():
    """Eight chips of 8 experts each: their parts less the shared
    experts, plus the shared experts once, are the whole 64-expert
    layer of the reference; and each share's part is the reference's
    for the experts it holds."""
    from repro.models.lm.layers import apply_mlp
    from repro.models.lm.moe import apply_moe
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 32))
    full_cfg, full = _layer(None)
    with jax.default_matmul_precision("highest"):
        shared = apply_mlp(full["shared"], x, "swiglu")
        total = shared
        for s in range(8):
            held = tuple(range(8 * s, 8 * s + 8))
            cfg, part = _layer(held)
            y, aux = apply_moe(part, x, cfg, "swiglu")
            want, _ = _plain_layer(part, x, held, 16)
            np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                       rtol=2e-5, atol=2e-6)
            total = total + (y - shared)
        whole, _ = _plain_layer(full, x, range(64), 16)
        uncut, _ = apply_moe(full, x, full_cfg, "swiglu")
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)


def test_no_token_dropped_under_skewed_routing():
    """Every token picks the same 6 held experts (a bias no score can
    beat): all T * 6 picks land here, the most the layer can get, and the
    output is the reference's, every token through its experts."""
    from repro.models.lm.moe import apply_moe
    held = tuple(range(8))
    cfg, p = _layer(held)
    p["router_bias"] = jnp.zeros((64,)).at[:6].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 32))
    with jax.default_matmul_precision("highest"):
        y, aux = apply_moe(p, x, cfg, "swiglu")
        want, _ = _plain_layer(p, x, held, 16)
    np.testing.assert_array_equal(np.asarray(aux["picks"]),
                                  [256] * 6 + [0, 0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.fixture(scope="module")
def tiny_spec(tiny):
    """The tiny cell's spec, with its workload registered for as long as
    the module's tests run."""
    from repro.core import workload
    workload.register_workload(tiny["workload"],
                               lambda: tiny_workload(tiny))
    base = harness.load_spec("mlp-fedavg_sched-c10s10-g13")
    plain = harness._module(os.path.join(HERE, "moonlight_tiny.py"),
                            "bench_test_moonlight_tiny")
    yield dict(base, cfg=tiny, model=plain,
               mix=harness._json(HERE, "c2s5-fedavg_sched.json"),
               limits=harness._json(HERE, "limits.json"))
    workload._BUILDERS.pop(tiny["workload"], None)
    workload._CACHE.pop(tiny["workload"], None)


@pytest.mark.parametrize("streamed,unwritten", [(False, "zeros"),
                                                (True, "zeros"),
                                                (True, "nan")])
def test_sound_run_is_correct(tiny_spec, streamed, unwritten, monkeypatch):
    """Stacked (all 10 clients fit) and streamed one client at a time,
    the latter also with the grouped matmul's unwritten rows NaN."""
    from repro.sim import engine
    if streamed:
        monkeypatch.setattr(engine, "copies_fit", lambda *a: False)
    if unwritten == "nan":
        unwritten_rows_read_nan(monkeypatch)
    out = small.run(tiny_spec)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(small.FAULTS))
def test_fault_is_not_correct(tiny_spec, fault, monkeypatch):
    small.FAULTS[fault](monkeypatch)
    out = small.run(tiny_spec)
    assert not out["correct"], out["checks"]
