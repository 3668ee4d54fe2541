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
# runs, as the stacked host path computed them before rounds could be
# streamed (CPU backend).
FEMNIST_HOST_DIGESTS = {
    "fedavg": "1de07b72bc75884c3265cd5411980132f96aee6a4c0d5b6f271107e0dba40c65",
    "fedavg_sched": "c5d0559a00958b797e4429fa440a258663fb59c54756361db0c3f91e6784c3db",
    "fedprox": "28ab603233e83e279fcfc284ae29afd37f7357a678f050f98194ac6b06a1f945",
    "fedbuff": "90b1f2b6ed1a0e71d64e8c76f20cd4df50a8bda44f1bc34efb5745e6bd09ea16",
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
