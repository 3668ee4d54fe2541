"""Batched scenario sweeps — many `ConstellationSim` scenarios per device call.

The paper's evidence is a 768-configuration grid; the loop path runs it
one jitted sim at a time, so every scenario pays its own XLA compiles and
its own Python round loop. This module executes a whole scenario *batch*
(same workload, different algorithms / constellations / station networks)
in two phases:

  1. **Host-side per-scenario planning** (timing phase). Orbital timing is
     training-independent: selection and round boundaries depend only on
     access windows / contact plans / the hardware cost model, never on
     gradient values. So each scenario's schedule — the (scenario, round,
     client) participation/epochs/staleness tables the device loop
     consumes — is produced by a timing-only twin of its engine run and is
     *bitwise* the loop path's `RoundRecord`s. Synchronous no-relay
     scenarios don't even run their twins: `_plan_sync_batched` advances
     all of them in lockstep over one scenario-stacked `WindowTable`
     (`WindowTable.stack` of per-scenario ground tables), replaying the
     selector arithmetic as batched array ops — bitwise-equal plans,
     one `first_live` binary search per (round, query) for the whole
     batch instead of a Python bisect per candidate. Relay-enabled,
     plan-backed, and async scenarios fall back to their scalar twins.

  2. **On-device batched rounds** (training phase, `cfg.train=True`).
     Per-scenario init params are stacked along a new leading scenario
     axis; each round gathers a rectangular (scenario, client) slab of
     federated data shards on the device, from the rows the schedule
     names, uploaded once per call (`engine.resident_shards`), builds
     steps, weights, staleness, anchors and RNG keys from the schedule,
     and dispatches ONE jitted
     `vmap(vmapped_client_update)` — the same per-client function object
     the engine and `launch.fl_round` use — followed by one
     `vmap(weighted_delta_update)` masked aggregation (`server_lr=1`,
     `staleness=0` reduces it to the sync weighted average; FedBuff's
     discounted delta comes out natively, exactly as the mesh collective
     covers both). Padded clients carry zero steps + zero weight; finished
     scenarios ride along as all-zero rows, the aggregation's zero-total
     guard keeping their params frozen. RNG streams replay the engine's
     exactly (one split per trained round, `split(sub, n_participants)`
     over the *unpadded* count), so per-client updates match the loop
     path; aggregation order differs only in the delta-vs-average float
     path, keeping end-of-round params within the 1e-5 parity envelope
     the mesh path already set.

Evaluation replays the engine's `_eval` (same selector call at `t_end`,
same power-of-two padding, same jitted `eval_fn`) per scenario, including
the final-model evaluation on truncated runs (`ConstellationSim._final_eval`).

Constraints: one batch shares a workload and the training knobs
(`train`/`lr`/`batch_size`/`max_steps`); constellations, algorithms,
station networks, horizons and seeds are free per scenario. Strategies
must aggregate within the weighted-average / discounted-delta family
(same refusal as mesh execution); `record_params` is unsupported.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.comms.contact_plan import ContactOutlook, WindowTable
from repro.core.aggregation import weighted_delta_update
from repro.core.client import vmapped_client_update
from repro.core.selection import (
    MAX_PASS_SLIDES,
    BaseSelector,
    ClientPlan,
    ScheduleSelector,
)
from repro.core.strategies.base import ClientWorkMode, Strategy
from repro.obs import count, span, syncing
from repro.sim.engine import (
    ConstellationSim,
    buffer_weights,
    client_steps,
    resident_shards,
    run_span,
    sync_round_metrics,
    to_device,
    to_host,
    traced_jit_call,
)
from repro.sim.metrics import SimResult


def _fast_plannable(sim: ConstellationSim) -> bool:
    """Scenarios the lockstep batched planner covers: the synchronous
    no-relay AccessWindows path (fedavg/fedprox + sched variants) with
    stock scheduling hooks. Relay, ContactPlan-backed, async, and
    custom-hook (connectivity-aware) scenarios plan on their scalar
    twins — the lockstep planner reproduces the one-group round barrier,
    so a strategy that times rounds differently must run its own loop."""
    sel = sim.alg.selector
    strat = type(sim.alg.strategy)
    return (sim.alg.synchronous
            and sim.plan is None
            and not sel.use_relay
            and type(sel) in (BaseSelector, ScheduleSelector)
            and strat.admit is Strategy.admit
            and strat.should_flush is Strategy.should_flush
            and strat.next_sync_point is Strategy.next_sync_point
            and sim.constellation.n_sats >= 2)


def _ground_table(sim: ConstellationSim) -> WindowTable:
    """Per-satellite merged ground windows as a rectangular WindowTable.

    Rates are informational (the AccessWindows path prices transfers with
    the flat `hw.tx_time_s`); the table exists for its batched
    `first_live` window search.
    """
    return ContactOutlook.from_access(
        sim.aw, rate_bps=sim.hw.link_mbps * 1e6).ground


@dataclasses.dataclass
class _PlanState:
    """Lockstep planner state for one scenario."""

    idx: int                      # position in the sweep batch
    sim: ConstellationSim
    twin: ConstellationSim        # timing-configured engine (record reuse)
    rows: np.ndarray              # stacked-table row per satellite
    t: float = 0.0
    done: bool = False
    rounds: list = dataclasses.field(default_factory=list)
    curve: list = dataclasses.field(default_factory=list)

    @property
    def K(self) -> int:
        return self.sim.constellation.n_sats


def _plan_sync_batched(states: list[_PlanState], table: WindowTable) -> None:
    """Advance every scenario's synchronous round loop in lockstep.

    Each iteration plans round `len(state.rounds)` for every still-active
    scenario with batched window queries over the scenario-stacked table,
    reproducing `selection._plan_prefix`/`_plan_for` (AccessWindows
    branch, no relay) bitwise — same float64 arithmetic, same bounded
    download-fit retry, same sort keys — then finishes the round through
    the twin engine's `_finish_round` so `RoundRecord` construction is
    the loop path's own code.
    """
    W = table.starts.shape[1]

    def win(rows, i):
        wi = np.minimum(i, max(W - 1, 0))
        return table.starts[rows, wi], table.ends[rows, wi]

    # Per-scenario planning constants (floats precomputed exactly as the
    # scalar selector computes them, so lane arithmetic stays bitwise).
    consts = {}
    for st in states:
        sim = st.sim
        hw, alg, cfg = sim.hw, sim.alg, sim.cfg
        fixed = alg.strategy.work_mode is ClientWorkMode.FIXED_EPOCHS
        consts[st.idx] = dict(
            tx=hw.tx_time_s,
            ep_t=hw.epoch_time_s,
            fixed=fixed,
            eft=alg.local_epochs * hw.epoch_time_s,
            emn=max(alg.min_epochs, 1) * hw.epoch_time_s,
            cap=hw.max_local_epochs,
            minf=min(alg.min_epochs, hw.max_local_epochs),
            E=alg.local_epochs,
            schedule=alg.selector.schedule,
            c=alg.strategy.round_size(min(cfg.clients_per_round, st.K)),
            # Shared round-trip pricing: full-precision download +
            # codec-priced uplink (`ul` IS `tx` for the identity codec,
            # so seed lanes stay bitwise).
            ul=hw.ul_time_s,
            comm_b=hw.round_trip_bytes,
        )

    while True:
        act = []
        for st in states:
            if st.done:
                continue
            if len(st.rounds) >= st.sim.cfg.max_rounds \
                    or st.t >= st.sim.cfg.horizon_s:
                st.done = True
                continue
            act.append(st)
        if not act or W == 0:
            for st in act:
                st.done = True   # no scenario has any window at all
            break

        def lane(key, dtype=float):
            return np.concatenate([
                np.full(st.K, consts[st.idx][key], dtype) for st in act])

        rows = np.concatenate([st.rows for st in act])
        t_l = np.concatenate([np.full(st.K, st.t) for st in act])
        tx_l = lane("tx")
        counts = table.counts[rows]

        # --- download pass (bounded fit retry, = `_plan_prefix`) -------- #
        i = table.first_live(rows, t_l)
        valid = i < counts
        s_w, e_w = win(rows, np.where(valid, i, 0))
        rx_s = np.maximum(s_w, t_l)
        rx_e = rx_s + tx_l
        for _ in range(MAX_PASS_SLIDES):
            over = valid & (rx_e > e_w)
            if not over.any():
                break
            q = e_w + 1.0
            i_new = table.first_live(rows, q)
            ok_new = i_new < counts
            s2, e2 = win(rows, np.where(ok_new, i_new, 0))
            valid = np.where(over, ok_new, valid)
            rx_s = np.where(over, np.maximum(s2, q), rx_s)
            rx_e = np.where(over, np.maximum(s2, q) + tx_l, rx_e)
            e_w = np.where(over, e2, e_w)
            i = np.where(over, i_new, i)
        valid &= ~(rx_e > e_w)   # retries exhausted: drop the candidate

        # --- training span + return window (= `_plan_for`, no relay) ---- #
        after = e_w + 1.0
        fixed_l = lane("fixed", bool)
        train_s = rx_e
        er = np.where(fixed_l,
                      np.maximum(rx_e + lane("eft"), after),
                      np.maximum(rx_e + lane("emn"), after))
        j = table.first_live(rows, er)
        rvalid = j < counts
        s_r, _ = win(rows, np.where(rvalid, j, 0))
        tx_s = np.maximum(s_r, er)
        tx_e = tx_s + lane("ul")   # return leg: codec-priced uplink
        valid &= rvalid
        # UNTIL_CONTACT epoch count: whole epochs in [train_start,
        # departure), duty-cycle capped, min-epoch floored, `or 1`.
        eb = (np.maximum(0.0, tx_s - train_s) / lane("ep_t")).astype(np.int64)
        eb = np.minimum(eb, lane("cap", np.int64))
        epu = np.maximum(eb, lane("minf", np.int64))
        epu = np.where(epu == 0, 1, epu)
        epochs_l = np.where(fixed_l, lane("E", np.int64), epu)
        train_e = np.where(fixed_l, rx_e + lane("eft"), tx_s)

        lo = 0
        for st in act:
            sl = slice(lo, lo + st.K)
            lo += st.K
            cn = consts[st.idx]
            plans = []
            for k in np.flatnonzero(valid[sl]):
                g = sl.start + int(k)
                plans.append(ClientPlan(
                    k=int(k), rx_start=float(rx_s[g]),
                    rx_end=float(rx_e[g]), train_start=float(train_s[g]),
                    train_end=float(train_e[g]), epochs=int(epochs_l[g]),
                    tx_start=float(tx_s[g]), tx_end=float(tx_e[g]),
                    comm_bytes=cn["comm_b"]))
            key = (lambda p: (p.tx_end, p.rx_start)) if cn["schedule"] \
                else (lambda p: (p.rx_start, p.tx_end))
            plans.sort(key=key)
            plans = plans[: min(cn["c"], len(plans))]
            r = len(st.rounds)
            with span("sim.round", idx=r, mode="batched_plan") as rs:
                if not plans:
                    rs.set(aborted="no_plans")
                    st.done = True
                    continue
                t_end = max(p.tx_end for p in plans)
                if t_end > st.sim.cfg.horizon_s:
                    rs.set(aborted="horizon")
                    st.done = True
                    continue
                st.twin._finish_round(
                    st.rounds, st.curve, None,
                    do_eval=(r % st.sim.cfg.eval_every == 0
                             or r == st.sim.cfg.max_rounds - 1),
                    **sync_round_metrics(plans, st.t, t_end))
                st.t = t_end


class BatchedSweep:
    """Plan + execute a batch of `ConstellationSim` scenarios together.

    `run()` returns one `SimResult` per input sim, in order. Timing-only
    batches (`cfg.train=False`) return after the planning phase —
    records bitwise the loop path's; training batches additionally run
    the stacked device rounds and carry accuracy curves + final params
    (1e-5 parity with the loop path, the mesh-execution envelope).
    """

    def __init__(self, sims: list[ConstellationSim],
                 names: list[str] | None = None, *,
                 batched_planning: bool = True):
        if not sims:
            raise ValueError("BatchedSweep needs at least one scenario")
        self.sims = list(sims)
        self.names = (list(names) if names is not None
                      else [f"scenario{i}" for i in range(len(sims))])
        if len(self.names) != len(self.sims):
            raise ValueError("names/sims length mismatch")
        self.batched_planning = batched_planning
        ref = self.sims[0]
        self.workload = ref.workload
        self.train = ref.cfg.train
        knobs = (ref.cfg.train, ref.cfg.lr, ref.cfg.batch_size,
                 ref.cfg.max_steps)
        from repro.core.strategies.base import Strategy
        from repro.core.strategies.fedbuff import FedBuffSat
        for sim, name in zip(self.sims, self.names):
            if sim.workload.name != self.workload.name:
                raise ValueError(
                    f"scenario {name!r} runs workload "
                    f"{sim.workload.name!r}; the batch stacks "
                    f"{self.workload.name!r} parameter trees — sweep one "
                    "workload per batch")
            if (sim.cfg.train, sim.cfg.lr, sim.cfg.batch_size,
                    sim.cfg.max_steps) != knobs:
                raise ValueError(
                    f"scenario {name!r} differs in train/lr/batch_size/"
                    "max_steps; the batched round core compiles one "
                    "update for the whole batch")
            if sim.cfg.record_params:
                raise ValueError("record_params is unsupported under "
                                 "BatchedSweep (parity harness: use the "
                                 "loop path)")
            if self.train and sim.workload.loss_has_aux:
                raise ValueError(
                    f"scenario {name!r}: workload {sim.workload.name!r} "
                    "returns per-step statistics with its loss; the "
                    "batched sweep carries parameters only — run it "
                    "through the loop path")
            if sim.execution == "mesh":
                raise ValueError(
                    f"scenario {name!r} requests mesh execution; the "
                    "batched sweep is its own vmapped executor — run "
                    "mesh scenarios through the loop path")
            agg = type(sim.alg.strategy).aggregate
            if self.train and agg not in (Strategy.aggregate,
                                          FedBuffSat.aggregate):
                raise ValueError(
                    f"strategy {sim.alg.strategy.name!r} overrides "
                    "aggregate() outside the weighted-average / "
                    "staleness-discounted-delta family; the batched "
                    "masked-delta aggregation would bypass it")
            # One codec per training batch: the codec transform is baked
            # into the single compiled round slab (a per-lane codec would
            # need one compile per codec anyway — sweep them as batches).
            if self.train and sim.codec.name != ref.codec.name:
                raise ValueError(
                    f"scenario {name!r} uses codec {sim.codec.name!r} but "
                    f"the batch compiles {ref.codec.name!r}; sweep one "
                    "codec per training batch")
        self.codec = ref.codec
        self._updaters: dict[tuple[int, int], object] = {}
        self._agg = None
        self._codec_rt = None

    # ------------------------------------------------------------------ #
    # Phase 1: host-side per-scenario planning                           #
    # ------------------------------------------------------------------ #
    def _twin(self, sim: ConstellationSim) -> ConstellationSim:
        cfg = dataclasses.replace(sim.cfg, train=False, record_params=False)
        return ConstellationSim(
            sim.constellation, sim.stations, sim.alg, data=sim.data,
            hw=sim.hw, cfg=cfg, access=sim.aw, contact_plan=sim.plan,
            workload=sim.workload, execution="host")

    def plan(self) -> tuple[list[SimResult], list[ConstellationSim]]:
        """Timing phase: one schedule (= loop-path records) per scenario."""
        S = len(self.sims)
        results: list[SimResult | None] = [None] * S
        twins: list[ConstellationSim | None] = [None] * S
        fast = [i for i, sim in enumerate(self.sims)
                if self.batched_planning and _fast_plannable(sim)]
        with span("sim.batched.plan", scenarios=S, lockstep=len(fast)):
            if fast:
                tables = [_ground_table(self.sims[i]) for i in fast]
                table, offs = WindowTable.stack(tables)
                states = []
                for j, i in enumerate(fast):
                    twin = self._twin(self.sims[i])
                    twins[i] = twin
                    states.append(_PlanState(
                        idx=i, sim=self.sims[i], twin=twin,
                        rows=int(offs[j])
                        + np.arange(self.sims[i].constellation.n_sats)))
                _plan_sync_batched(states, table)
                for st in states:
                    results[st.idx] = st.twin._result(st.rounds, st.curve,
                                                      None)
            for i, sim in enumerate(self.sims):
                if results[i] is not None:
                    continue
                twin = self._twin(sim)
                twins[i] = twin
                with span("sim.batched.plan_scalar", scenario=self.names[i]):
                    results[i] = twin.run()
        return results, twins

    # ------------------------------------------------------------------ #
    # Phase 2: stacked device rounds                                     #
    # ------------------------------------------------------------------ #
    def _updater(self, bound: int, c_pad: int):
        key = (bound, c_pad)
        if key not in self._updaters:
            inner = vmapped_client_update(
                self.workload.loss_fn, lr=self.sims[0].cfg.lr,
                batch_size=self.sims[0].cfg.batch_size, max_steps=bound,
                anchored=True)
            self._updaters[key] = jax.jit(jax.vmap(inner, in_axes=(0,) * 8))
        return self._updaters[key]

    def _aggregate(self):
        if self._agg is None:
            self._agg = jax.jit(jax.vmap(weighted_delta_update,
                                         in_axes=(0, 0, 0, 0, 0)))
        return self._agg

    def _codec_roundtrip(self):
        """Jitted (scenario, client)-vmapped codec round-trip — the same
        per-client `client_roundtrip` the loop engine and mesh step apply,
        lifted over the batch axis. Padded clients and finished scenarios
        decode garbage that the zero-weight mask then discards."""
        if self._codec_rt is None:
            from repro.comms.codec import client_roundtrip
            one = client_roundtrip(self.codec)
            self._codec_rt = jax.jit(jax.vmap(
                jax.vmap(one, in_axes=(0, 0, 0)), in_axes=(0, 0, 0)))
        return self._codec_rt

    def run(self) -> list[SimResult]:
        with run_span(execution="batched", scenarios=len(self.sims)):
            planned, twins = self.plan()
            if not self.train:
                return planned
            return self._train_batch(planned, twins)

    def _train_batch(self, planned: list[SimResult],
                     twins: list[ConstellationSim]) -> list[SimResult]:
        sims = self.sims
        # Scenarios with K < 2 never federate (their loop result is the
        # empty record set with no params); pass their planned result
        # through untouched and stack the rest.
        fed = [i for i in range(len(sims))
               if sims[i].constellation.n_sats >= 2]
        if not fed:
            return planned
        B = len(fed)
        results = list(planned)

        # RNG replay: PRNGKey(seed) -> init split -> one split per trained
        # round — the engine's exact stream per scenario.
        params0, subs, n_rounds = [], [], []
        for b, i in enumerate(fed):
            sim = sims[i]
            rng = jax.random.PRNGKey(sim.cfg.seed)
            rng, init_rng = jax.random.split(rng)
            params0.append(sim.init_fn(init_rng))
            rs = []
            for _ in planned[i].rounds:
                rng, sub = jax.random.split(rng)
                rs.append(sub)
            subs.append(rs)
            n_rounds.append(len(planned[i].rounds))
        G = jax.tree.map(lambda *xs: jnp.stack(xs), *params0)

        R = max(n_rounds, default=0)
        if R == 0:
            for b, i in enumerate(fed):
                results[i] = dataclasses.replace(
                    planned[i], execution="batched",
                    final_params=to_host(
                        jax.tree.map(lambda l, b=b: l[b], G)))
            return results

        c_max = max((len(rec.participants) for i in fed
                     for rec in planned[i].rounds), default=1)
        C = ConstellationSim._bound([c_max])
        N = max(sims[i].data.x.shape[1] for i in fed)
        # The training shards go up once for this call: of each distinct
        # dataset array, the rows some planned round names. `place` maps a
        # dataset to its rows and where they start on the device.
        named: dict[int, tuple] = {}
        for i in fed:
            data = sims[i].data
            _, used = named.setdefault(id(data.x), (data, set()))
            used.update(k for rec in planned[i].rounds
                        for k in rec.participants)
        parts = [(d, np.array(sorted(used)))
                 for d, used in named.values() if used]
        place, n_rows = {}, 0
        for d, rows in parts:
            place[id(d.x)] = (rows, n_rows)
            n_rows += len(rows)

        # Per-(batch,round) max staleness → how far back anchors reach;
        # a suffix-min over rounds bounds the history the executor keeps.
        vmin_r = np.full(R, np.iinfo(np.int64).max)
        for b, i in enumerate(fed):
            for r, rec in enumerate(planned[i].rounds):
                lag = max(rec.staleness, default=0)
                vmin_r[r] = min(vmin_r[r], r - lag)
        vmin_r = np.minimum(vmin_r, np.arange(R))
        keep_from = np.minimum.accumulate(vmin_r[::-1])[::-1]

        hist = {0: G}
        curves: list[list] = [[] for _ in fed]
        agg = self._aggregate()
        # Sync strategies aggregate with weighted_average, which has no
        # server-lr knob — pin 1.0 so the delta form reduces to it exactly.
        slr = to_device(
            [1.0 if sims[i].alg.synchronous
             else getattr(sims[i].alg.strategy, "server_lr", 1.0)
             for i in fed], jnp.float32)
        prox = to_device([sims[i].alg.strategy.prox_mu for i in fed],
                         jnp.float32)

        for r in range(R):
            active = [b for b in range(B) if r < n_rounds[b]]
            with span("sim.batched.assemble", round=r,
                      scenarios=len(active)):
                if r == 0:
                    gather = resident_shards(parts, N)
                steps = np.zeros((B, C), np.int32)
                w = np.zeros((B, C), np.float32)
                stale = np.zeros((B, C), np.int32)
                vs = np.full((B, C), r, np.int64)
                # Device rows of the slab; row n_rows, past the end, reads
                # as zeros, as a finished scenario's lanes always have.
                rows_bc = np.full((B, C), n_rows, np.int32)
                rngs = np.zeros((B, C, 2), np.uint32)
                for b in active:
                    sim = sims[fed[b]]
                    rec = results[fed[b]].rounds[r]
                    ks = rec.participants
                    n = len(ks)
                    ks_p = list(ks) + [ks[0]] * (C - n)
                    data = sim.data
                    st = np.asarray(rec.staleness, np.int64)
                    steps[b, :n] = [client_steps(int(data.n[k]), e,
                                                 sim.cfg.batch_size,
                                                 sim.cfg.max_steps)
                                    for k, e in zip(ks, rec.epochs)]
                    ns = np.asarray([float(data.n[k]) for k in ks],
                                    np.float32)
                    if sim.alg.synchronous:
                        w[b, :n] = ns
                    else:
                        w[b, :n] = buffer_weights(
                            ns, st.astype(np.int32),
                            sim.alg.strategy.max_staleness)
                        stale[b, :n] = st
                        vs[b, :n] = r - st
                    rows, start = place[id(data.x)]
                    rows_bc[b] = start + np.searchsorted(rows, ks_p)
                    rr = to_host(jax.random.split(subs[b][r], n))
                    rngs[b, :n] = rr
                    if C > n:
                        rngs[b, n:] = rr[0]
            bound = ConstellationSim._bound(np.maximum(steps, 1))
            update = self._updater(bound, C)

            with span("sim.round", idx=r, mode="batched",
                      scenarios=len(active)):
                v_lo = int(keep_from[r])
                if int(vs.min()) >= r:
                    anchors = jax.tree.map(
                        lambda g: jnp.broadcast_to(
                            g[:, None], (B, C) + g.shape[1:]), G)
                else:
                    vstk = jax.tree.map(
                        lambda *xs: jnp.stack(xs),
                        *[hist[v] for v in range(v_lo, r + 1)])
                    vrel = to_device(vs - v_lo)
                    bidx = jnp.arange(B)[:, None]
                    anchors = jax.tree.map(lambda hv: hv[vrel, bidx], vstk)
                with span("sim.client_train", mode="batched",
                          scenarios=len(active), step_bound=bound) as sp:
                    keys = to_device(rngs)
                    x, y, nv = gather(rows_bc)
                    out = traced_jit_call(
                        sp, update, anchors, anchors, x, y, nv,
                        to_device(steps), prox, keys)
                    if self.codec.lossy:
                        # Same per-client codec round-trip as the loop
                        # engine (same rng keys: split(sub, n) rows), so
                        # the decoded returns match client for client.
                        out = self._codec_roundtrip()(out, anchors, keys)
                    if syncing():
                        jax.block_until_ready(out)
                with span("sim.aggregate", mode="batched",
                          scenarios=len(active)):
                    G = agg(G, out, to_device(w), to_device(stale), slr)
                    if syncing():
                        jax.block_until_ready(G)
                hist[r + 1] = G
                if r + 1 < R:
                    lo = int(keep_from[r + 1])
                    for v in [v for v in hist if v < lo]:
                        del hist[v]
                else:
                    hist.clear()

                for b in active:
                    i = fed[b]
                    sim, rec = sims[i], results[i].rounds[r]
                    if sim.alg.synchronous:
                        do_eval = (r % sim.cfg.eval_every == 0
                                   or r == sim.cfg.max_rounds - 1)
                    else:
                        do_eval = r % sim.cfg.eval_every == 0
                    # Truncated runs evaluate their final model too —
                    # the engine's exit-path eval (`_final_eval`).
                    do_eval = do_eval or r == n_rounds[b] - 1
                    if not do_eval:
                        continue
                    pb = jax.tree.map(lambda l, b=b: l[b], G)
                    with span("sim.eval", round=r, trained=True,
                              mode="batched"):
                        rec.accuracy = twins[i]._eval(pb, rec.t_end)
                        curves[b].append((r, rec.t_end, rec.accuracy))
                        count("sim.evals")

        for b, i in enumerate(fed):
            results[i] = dataclasses.replace(
                results[i], accuracy_curve=curves[b], execution="batched",
                final_params=to_host(
                    jax.tree.map(lambda l, b=b: l[b], G)))
        return results


def run_batched(sims: list[ConstellationSim],
                names: list[str] | None = None, **kwargs) -> list[SimResult]:
    """One-call convenience: `BatchedSweep(sims, names).run()`."""
    return BatchedSweep(sims, names, **kwargs).run()
