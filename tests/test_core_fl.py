"""Space-ified FL core: aggregation math, selection protocols, timing."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import (
    ALGORITHMS,
    BaseSelector,
    FedAvgSat,
    FedBuffSat,
    FedProxSat,
    IntraCCSelector,
    ScheduleSelector,
    spaceify,
)
from repro.core.aggregation import (
    normalized_weights,
    weighted_average,
    weighted_delta_update,
)
from repro.core.timing import HardwareModel
from repro.orbits import WalkerStar, compute_access_windows, station_subnetwork


@pytest.fixture(scope="module")
def access():
    c = WalkerStar(2, 5)
    return c, compute_access_windows(c, station_subnetwork(3),
                                     horizon_s=5 * 86400.0)


# ---------------------------------------------------------------- math --
@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.integers(1, 5))
def test_weighted_average_convexity(k, dims):
    """Aggregate of identical models is the model; weights normalize."""
    rng = np.random.default_rng(k * 10 + dims)
    base = {"a": jnp.asarray(rng.normal(size=(dims, 3)), jnp.float32)}
    stacked = jax.tree.map(lambda x: jnp.stack([x] * k), base)
    w = jnp.asarray(rng.integers(100, 400, size=k), jnp.float32)
    out = weighted_average(stacked, w)
    np.testing.assert_allclose(np.asarray(out["a"]),
                               np.asarray(base["a"]), rtol=1e-5)


def test_weighted_average_matches_eq1():
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(3, 7)), jnp.float32)
    n = jnp.asarray([200.0, 300.0, 350.0])
    out = weighted_average({"w": xs}, n)["w"]
    ref = (n[:, None] / n.sum() * xs).sum(0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_zero_weight_round_keeps_model():
    xs = {"w": jnp.ones((4, 5))}
    out = weighted_delta_update({"w": jnp.zeros(5)}, xs,
                                jnp.zeros(4), jnp.zeros(4, jnp.int32))
    np.testing.assert_allclose(np.asarray(out["w"]), 0.0)


def test_fedbuff_staleness_discount():
    g = {"w": jnp.zeros(3)}
    xs = {"w": jnp.stack([jnp.ones(3), jnp.ones(3)])}
    fresh = weighted_delta_update(g, xs, jnp.ones(2),
                                  jnp.asarray([0, 0]))
    stale = weighted_delta_update(g, xs, jnp.ones(2),
                                  jnp.asarray([8, 8]))
    # Normalized weights cancel uniform discounts on the mean, but the
    # FedBuff admission bound is enforced upstream; mixed staleness tilts
    # toward the fresh client:
    mixed = weighted_delta_update(g, {"w": jnp.stack(
        [jnp.ones(3), 3 * jnp.ones(3)])}, jnp.ones(2),
        jnp.asarray([0, 8]))
    assert float(mixed["w"][0]) < 2.0  # fresh (=1) outweighs stale (=3)
    np.testing.assert_allclose(np.asarray(fresh["w"]), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(stale["w"]), 1.0, rtol=1e-6)


def test_strategy_staleness_admission():
    assert FedBuffSat().staleness_ok(4)
    assert not FedBuffSat().staleness_ok(5)
    assert FedAvgSat().staleness_ok(0)
    assert not FedAvgSat().staleness_ok(1)


def _returns(seed, k=6):
    """A global model and k returns around it, MLP-shaped leaves. Every
    entry lies in [0.5, 1.5], so none cancels to near zero in the
    weighted sum and a relative tolerance holds."""
    rng = np.random.default_rng(seed)
    shapes = {"w1": (784, 56), "b1": (56,), "w2": (56, 47), "b2": (47,)}
    g = {n: jnp.asarray(rng.uniform(0.5, 1.5, size=s), jnp.float32)
         for n, s in shapes.items()}
    stacked = {n: g[n][None] + jnp.asarray(
        0.01 * rng.normal(size=(k,) + s), jnp.float32)
        for n, s in shapes.items()}
    return g, stacked


@pytest.mark.parametrize("strategy,weights,staleness", [
    # FedAvg: sample counts, no staleness
    (FedAvgSat(), [210., 340., 256., 301., 222., 288.], [0] * 6),
    # FedBuff, mixed staleness; the over-stale client already weighs zero
    (FedBuffSat(server_lr=0.7), [210., 340., 0., 301., 222., 288.],
     [0, 1, 9, 2, 3, 0]),
    # an empty round: every weight zero, the guard keeps the old model
    (FedAvgSat(), [0.] * 6, [0] * 6),
], ids=["fedavg", "fedbuff_stale", "all_zero"])
def test_jitted_aggregate_matches_eager(strategy, weights, staleness):
    """The host loop runs `aggregate` as one jitted program: XLA fuses
    each weighted sum, and may order it otherwise, never by more than
    rounding."""
    g, stacked = _returns(len(weights) + sum(staleness))
    args = (g, stacked, jnp.asarray(weights, jnp.float32),
            jnp.asarray(staleness, jnp.int32))
    eager = strategy.aggregate(*args)
    jitted = jax.jit(lambda *a: strategy.aggregate(*a))(*args)
    for e, j in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted),
                    strict=True):
        assert j.dtype == e.dtype and j.shape == e.shape
        np.testing.assert_allclose(j, e, rtol=1e-6, atol=0)


# ------------------------------------------------------------ selection --
def test_selection_counts_and_order(access):
    c, aw = access
    hw = HardwareModel()
    for sel in (BaseSelector(), ScheduleSelector(), IntraCCSelector()):
        plans = sel.select(aw, 0.0, range(c.n_sats), 5, FedAvgSat(), hw,
                           local_epochs=5)
        assert len(plans) == 5
        ks = [p.k for p in plans]
        assert len(set(ks)) == 5
        for p in plans:
            assert p.rx_start >= 0 and p.tx_end > p.rx_start
            assert p.train_end >= p.train_start
            assert p.epochs >= 1


def test_scheduler_no_worse_than_base(access):
    """FLSchedule picks fastest-returning clients: the slowest selected
    return time can only improve vs contact-order selection."""
    c, aw = access
    hw = HardwareModel()
    base = BaseSelector().select(aw, 0.0, range(c.n_sats), 5, FedAvgSat(),
                                 hw, local_epochs=5)
    sched = ScheduleSelector().select(aw, 0.0, range(c.n_sats), 5,
                                      FedAvgSat(), hw, local_epochs=5)
    assert max(p.tx_end for p in sched) <= max(p.tx_end for p in base) + 1e-6


def test_intracc_relay_helps(access):
    """With relays enabled a satellite's return time never gets worse."""
    c, aw = access
    hw = HardwareModel()
    base = {p.k: p for p in BaseSelector().select(
        aw, 0.0, range(c.n_sats), c.n_sats, FedAvgSat(), hw, 5)}
    icc = {p.k: p for p in IntraCCSelector().select(
        aw, 0.0, range(c.n_sats), c.n_sats, FedAvgSat(), hw, 5)}
    for k in icc:
        if k in base:
            assert icc[k].tx_end <= base[k].tx_end + 1e-6


def test_until_contact_trains_through_gap(access):
    c, aw = access
    hw = HardwareModel()
    plans = BaseSelector().select(aw, 0.0, range(c.n_sats), 3,
                                  FedProxSat(), hw, local_epochs=5)
    for p in plans:
        # Algorithm 2: training spans the whole inter-pass gap.
        assert p.train_end == pytest.approx(p.tx_start)
        assert p.epochs >= 1


def test_return_is_next_pass(access):
    """Parameters return at a later pass, never the download pass."""
    c, aw = access
    hw = HardwareModel()
    for alg in (FedAvgSat(), FedProxSat()):
        for p in BaseSelector().select(aw, 0.0, range(c.n_sats), 5, alg,
                                       hw, 5):
            w = aw.next_window(p.k, p.rx_start)
            assert p.tx_start >= w[1], "upload must wait for a later pass"


def test_relay_peer_beats_own_return_window(access):
    """A relay is only assigned when the peer's ground window opens
    STRICTLY before the training satellite's own next pass (the original
    satellite keeps priority on ties)."""
    c, aw = access
    hw = HardwareModel()
    plans = IntraCCSelector().select(aw, 0.0, range(c.n_sats), c.n_sats,
                                     FedAvgSat(), hw, 5)
    relayed = [p for p in plans if p.relay != -1]
    assert relayed, "a 5-per-plane cluster over 3 stations must relay some"
    for p in plans:
        own = aw.next_window(p.k, max(p.train_end,
                                      aw.next_window(p.k, p.rx_start)[1] + 1.0))
        if p.relay != -1:
            assert p.relay in aw.cluster_members(p.k)
            assert p.relay != p.k
            assert p.relay_path == (p.k, p.relay)
            # The relayed upload must start before the own-satellite pass.
            if own is not None:
                assert p.tx_start < own[0]
        elif own is not None:
            # No relay assigned: the own pass was never beaten.
            assert p.tx_start <= own[0] + 1e-6


# ------------------------------------------------------------- registry --
def test_algorithm_suite_is_papers_table1():
    from repro.core import TABLE1_ALGORITHMS
    assert set(TABLE1_ALGORITHMS) == {
        "fedavg", "fedavg_sched", "fedavg_intracc",
        "fedprox", "fedprox_sched", "fedprox_sched_v2", "fedprox_intracc",
        "fedbuff",
    }
    # The registered suite = Table 1 + the ISL-priced relay extensions
    # + the connectivity-aware strategies from the related work.
    assert set(ALGORITHMS) == set(TABLE1_ALGORITHMS) | {
        "fedavg_intracc_isl", "fedprox_intracc_isl",
        "fedspace", "ground_assisted", "fedprox_sparse",
    }
    assert not ALGORITHMS["fedbuff"].synchronous
    assert ALGORITHMS["fedprox_sched_v2"].min_epochs == 5
    assert ALGORITHMS["fedavg_intracc_isl"].isl
    assert not ALGORITHMS["fedavg_intracc"].isl


def test_spaceify_composition():
    alg = spaceify(FedProxSat(), schedule=True, intracc=True)
    assert isinstance(alg.selector, IntraCCSelector)
    assert alg.selector.schedule
    isl = spaceify(FedProxSat(), intracc=True, isl=True, max_hops=2)
    assert isl.name == "fedprox_intracc_isl"
    assert isl.isl and isl.selector.max_hops == 2


# ----------------------------------------------------- streamed rounds --
# A round whose clients' model copies do not fit the device together is
# trained one client after another in one program, each return folded
# into one accumulator (`ConstellationSim._stream_clients`). On the CPU
# every round fits, so the tests stream rounds themselves.
def _femnist_sim(alg, **kw):
    from repro.sim import ConstellationSim, SimConfig
    return ConstellationSim(
        WalkerStar(2, 5), station_subnetwork(13), ALGORITHMS[alg],
        workload="femnist_mlp",
        cfg=SimConfig(max_rounds=3, horizon_s=2 * 86400.0,
                      clients_per_round=10, eval_every=1, max_steps=16,
                      seed=7, **kw))


def _stream(monkeypatch):
    from repro.sim import engine
    monkeypatch.setattr(engine, "copies_fit", lambda *a: False)


@pytest.mark.parametrize("alg", ["fedavg", "fedavg_sched", "fedprox"])
@pytest.mark.parametrize("sync", [False, True])
def test_streamed_round_matches_the_stacked_aggregate(alg, sync,
                                                       monkeypatch):
    """Folding the clients one after another gives the stacked round's
    weighted average, with the same records, whether the tracer syncs
    or not; one `sim.client` span a round, carrying its clients and
    executed steps."""
    from repro import obs
    stacked = _femnist_sim(alg).run()
    _stream(monkeypatch)
    with obs.tracing(sync=sync) as tracer:
        streamed = _femnist_sim(alg).run()
    assert [r.participants for r in streamed.rounds] == \
        [r.participants for r in stacked.rounds]
    for a, b in zip(jax.tree.leaves(streamed.final_params),
                    jax.tree.leaves(stacked.final_params), strict=True):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    rounds = [s for s in tracer.events if s["name"] == "sim.client"]
    assert [g["args"]["clients"] for g in rounds] == [
        len(r.participants) for r in streamed.rounds]
    assert all(g["args"]["steps"] % g["args"]["clients"] == 0
               for g in rounds)
    assert not any(s["name"] == "sim.aggregate" for s in tracer.events)


# sha256 of the final parameters and the accuracy curve of `_femnist_sim`
# runs, as the stacked host path computes them with its server update as
# one jitted program (CPU backend). The update run op by op gave the same
# accuracy curves, and final parameters within 3.3e-7 of each leaf's
# largest entry: XLA fuses each weighted sum and orders it otherwise.
FEMNIST_HOST_DIGESTS = {
    "fedavg": "5aecc5cbfdee1d361072f6cf0bbc4f0e6184435ab0883cfdf09e007db8e34232",
    "fedavg_sched": "f7da945e19118bf766096bc17905301491c3dcb79652ca6c14424de4b2bd3c04",
    "fedprox": "2353a86dc82949b98578835f4ad7d473d972ea721d2da2bc4a5325f979a4fcfa",
    "fedbuff": "400145cfc04844950efe2e58f7c6786a090b676703986f615c4d070bd618a830",
}


@pytest.mark.parametrize("alg", sorted(FEMNIST_HOST_DIGESTS))
def test_femnist_host_path_keeps_its_result_bit_for_bit(alg):
    import hashlib
    res = _femnist_sim(alg).run()
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(res.final_params):
        h.update(np.ascontiguousarray(leaf).tobytes())
    h.update(repr([a for _, _, a in res.accuracy_curve]).encode())
    assert h.hexdigest() == FEMNIST_HOST_DIGESTS[alg]


def test_streaming_refuses_what_it_does_not_fold(monkeypatch):
    """Buffered rounds anchored on older versions, and a strategy with an
    aggregate of its own, are refused when their round is streamed."""
    _stream(monkeypatch)
    with pytest.raises(ValueError, match="anchored"):
        _femnist_sim("fedbuff").run()

    class Median(FedAvgSat):
        def aggregate(self, global_params, client_params, weights,
                      staleness):
            return jax.tree.map(lambda x: jnp.median(x, axis=0),
                                client_params)
    with pytest.raises(ValueError, match="weighted average"):
        from repro.sim import ConstellationSim, SimConfig
        ConstellationSim(
            WalkerStar(2, 5), station_subnetwork(13),
            spaceify(Median(), name="median"), workload="femnist_mlp",
            cfg=SimConfig(max_rounds=1, horizon_s=2 * 86400.0,
                          max_steps=4)).run()


@pytest.mark.parametrize("limit,fits", [
    (None, True), (0, True), (20_000_000, False), (100_000_000, True),
    (64_000_000, True), (63_999_999, False)])
def test_copies_fit_follows_the_device_memory(limit, fits):
    """Every round fits where the device reports no limit; otherwise the
    global model, the result and three copies a client have to fit in
    half the device."""
    from repro.sim import engine
    params = {"w": np.zeros((250_000,), np.float32)}       # 1 MB
    assert engine.copies_fit(params, 10, limit) is fits


def test_mesh_and_batched_refuse_a_loss_with_statistics():
    from repro.core.workload import lm_workload
    from repro.models.lm.config import ModelConfig, MoEConfig
    from repro.sim import BatchedSweep, ConstellationSim, SimConfig
    cfg = ModelConfig(name="moe", arch_type="moe", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=32,
                      moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=8),
                      dtype="float32")
    wl = lm_workload(cfg, name="moe_stats", seq_len=8,
                     with_routing_stats=True)
    kw = dict(workload=wl, cfg=SimConfig(max_rounds=1,
                                         horizon_s=86400.0))
    with pytest.raises(ValueError, match="statistics"):
        ConstellationSim(WalkerStar(2, 5), station_subnetwork(3),
                         ALGORITHMS["fedavg"], execution="mesh", **kw)
    sim = ConstellationSim(WalkerStar(2, 5), station_subnetwork(3),
                           ALGORITHMS["fedavg"], **kw)
    with pytest.raises(ValueError, match="statistics"):
        BatchedSweep([sim])
