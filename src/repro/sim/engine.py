"""ConstellationSim — event-driven execution of a space-ified FL algorithm.

Couples four layers:
  * orbital geometry  (`repro.orbits`)     — who can talk to whom, when;
  * communications    (`repro.comms`)      — link rates, ISL contact
                                             windows, relay routing (built
                                             only for `isl=True` algorithms
                                             or explicit link models);
  * the FL algorithm  (`repro.core`)       — selection + client regime +
                                             aggregation;
  * the workload      (`repro.core.workload`) — model init/loss/eval, the
                                             batch schema, and the derived
                                             cost model (what the
                                             satellites actually train:
                                             FEMNIST classifiers, LM
                                             fine-tuning, ...).

One strategy-driven event loop (`_run_events`) executes every algorithm:
two event feeds — the synchronous selection barrier of Algorithms 1-2
and the asynchronous upload heap of Algorithm 3 — dispatch every
admission / flush / sync-point decision through the strategy's
scheduling hooks (`Strategy.admit` / `should_flush` /
`next_sync_point`), with a read-only `ContactOutlook` over the
scenario's contact schedule as the hooks' view of the future. The
default hooks reproduce the classic barrier and size-D buffer
semantics bitwise (tests/test_engine_parity.py pins every registry
algorithm's RoundRecords against the pre-refactor engine); overriding
them yields connectivity-aware round timing (FedSpace-style early
flushes, per-visit ground-assisted aggregation) without touching the
engine. Both feeds share one round-execution core (`_train_round` +
`_finish_round`) and produce the paper's three metrics per round:
accuracy, round duration, and per-satellite idle time.

`_train_round` dispatches on the execution mode (a `Workload` capability,
overridable per run with `ConstellationSim(..., execution=...)`):

  * "host" — the reference path: one jitted vmap over stacked clients,
    then `Strategy.aggregate` as one jitted program per client count; where
    the round's model copies do not fit the device together
    (`copies_fit`), one program trains its clients one after another and
    folds each return into one donated accumulator (`_stream_clients`);
  * "mesh" — cluster-as-collective (`launch.fl_round.make_mesh_round_step`):
    each participating satellite is a pod slot on a mesh axis, local SGD
    runs inside shard_map, and aggregation is a participation-masked psum.
    Covers every strategy in the (weighted-average / staleness-discounted
    weighted-delta, server-lr) family — i.e. the whole registered suite;
    a custom `Strategy.aggregate` outside that family must run on "host".
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import heapq
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.comms.contact_plan import (
    ContactOutlook,
    ContactPlan,
    build_contact_plan,
)
from repro.comms.isl import ISLTopology, compute_isl_windows
from repro.comms.links import ConstantRate, LinkModel
from repro.core.aggregation import (
    admission_weights,
    fold_weighted,
    normalized_weights,
)
from repro.core.client import (
    make_client_update,
    vmapped_client_update,
    zero_stats,
)
from repro.core.spaceify import SpaceifiedAlgorithm
from repro.core.strategies.base import BufferState, PendingUpdate, Strategy
from repro.core.timing import HardwareModel
from repro.core.workload import Workload, get_workload, validate_execution
from repro.data.federated import FederatedDataset
from repro.models.femnist_mlp import femnist_mlp_apply, femnist_mlp_init
from repro.obs import (
    count,
    enabled as obs_enabled,
    get_tracer,
    span,
    syncing,
)
from repro.orbits import constants as C
from repro.orbits.access import AccessWindows, compute_access_windows
from repro.orbits.walker import WalkerStar
from repro.sim.metrics import RoundRecord, SimResult


@dataclasses.dataclass(frozen=True)
class SimConfig:
    max_rounds: int = 500            # paper: 500-round cap
    horizon_s: float = 90 * 86400.0  # paper: 3-month scenario
    clients_per_round: int = 10      # C
    batch_size: int = 32
    lr: float = 0.05
    eval_every: int = 5              # rounds between evaluations
    max_steps: int = 128             # static bound on local SGD steps/round
    seed: int = 0
    train: bool = True               # False: timing-only sweep (no gradients)
    record_params: bool = False      # keep a per-round global-params history
                                     # (parity harness; costs host memory)


def client_steps(n_k: int, epochs: int, batch_size: int,
                 max_steps: int) -> int:
    """Local SGD steps for a client with `n_k` samples running `epochs`
    epochs: `epochs * max(1, n_k // batch_size)`, clipped to [1, max_steps].
    One formula shared by the loop engine and the batched scenario sweep
    (`repro.sim.batched`) so their step schedules cannot drift."""
    spe = max(1, n_k // batch_size)
    return int(np.clip(epochs * spe, 1, max_steps))


TRAFFIC_COUNTERS = ("sim.h2d_bytes", "sim.d2h_bytes", "sim.host_syncs",
                    "sim.gathered_rows")
# Counted from what the client program returns (`count_returned`).
ROUTING_COUNTERS = ("moe.local_picks", "moe.local_picks_max",
                    "moe.local_picks_mean", "moe.dropped")


def to_device(x, dtype=None) -> jax.Array:
    """`jnp.asarray(x, dtype)`. Where `x` is on the host this uploads it,
    and the bytes it takes on the device are counted in `sim.h2d_bytes`;
    a device array passes through uncounted."""
    out = jnp.asarray(x, dtype)
    if obs_enabled() and not isinstance(x, jax.Array):
        count("sim.h2d_bytes", out.nbytes)
    return out


@jax.jit
def _take_rows(shards, idx):
    return tuple(a.at[idx].get(mode="fill", fill_value=0) for a in shards)


def resident_shards(parts, samples: int | None = None):
    """Upload client training shards once; serve their rows by index.

    `parts` is a `FederatedDataset` (all of its clients) or a list of
    `(dataset, rows)` pairs, whose rows are stacked in order. Each part's
    `x`, `y` and `n` go up through `to_device`, so they are counted in
    `sim.h2d_bytes`; a part with fewer samples than `samples` (default:
    the most of any part) is zero-padded on the device.

    The returned `gather(idx) -> (x, y, n)` uploads only the int32 row
    numbers `idx`, of any shape, and takes those rows on the device. A
    row number past the last row reads as zeros. Each call counts
    `idx.size` in `sim.gathered_rows`.
    """
    if isinstance(parts, FederatedDataset):
        parts = [(parts, slice(None))]
    samples = samples or max(d.x.shape[1] for d, _ in parts)
    cols = []
    for d, rows in parts:
        x, y = to_device(d.x[rows]), to_device(d.y[rows])
        pad = samples - x.shape[1]
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            y = jnp.pad(y, [(0, 0), (0, pad)])
        cols.append((x, y, to_device(d.n[rows])))
    shards = tuple(c[0] if len(c) == 1 else jnp.concatenate(c)
                   for c in zip(*cols))

    def gather(idx):
        count("sim.gathered_rows", np.size(idx))
        return _take_rows(shards, to_device(idx, jnp.int32))

    return gather


def to_host(tree):
    """Read device values to the host (`jax.device_get`): one host sync,
    counted in `sim.host_syncs`, and its bytes in `sim.d2h_bytes`.

    Every device-to-host read of a run goes through here and is explicit,
    so an implicit one (`float(x)`, `np.asarray(x)`) stands out: on an
    accelerator it fails under
    `jax.transfer_guard_device_to_host("disallow")`."""
    out = jax.device_get(tree)
    if obs_enabled():
        count("sim.host_syncs")
        count("sim.d2h_bytes",
              sum(np.asarray(a).nbytes for a in jax.tree.leaves(out)))
    return out


def count_returned(stats) -> None:
    """Add the counters a client program returned (`Workload.loss_has_aux`
    stats, summed over its clients) to the tracer. Only under a syncing
    tracer, which waits on the device anyway: untraced runs read nothing
    back for them."""
    if stats and syncing():
        for name, v in to_host(stats).items():
            count(name, float(np.sum(v)))


@functools.cache
def device_bytes_limit() -> int | None:
    """The first device's memory limit; None where the backend reports
    none (the CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def copies_fit(params, n_clients: int, limit: int | None) -> bool:
    """Whether a round's `n_clients` stacked model copies fit a device of
    `limit` bytes. A stacked client holds about three copies of the model
    (its start, its carried state, its gradient); the global model and
    the result are two more, and half of the device is left for
    activations. No limit (the CPU) fits every round."""
    if not limit:
        return True
    model = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    return (2 + 3 * n_clients) * model <= limit / 2


@contextlib.contextmanager
def run_span(**args):
    """The `sim.run` span around one whole run (`ConstellationSim.run`,
    `BatchedSweep.run`). On a clean exit it carries the run's host-device
    traffic as args `h2d_bytes`, `d2h_bytes`, `host_syncs` and
    `gathered_rows`, and its experts' routing as `local_picks`,
    `local_picks_max`, `local_picks_mean` and `dropped`: how much each of `TRAFFIC_COUNTERS`
    and `ROUTING_COUNTERS` grew during the run (counters are global to
    the tracer; the args price one run from its span alone)."""
    tracer = get_tracer()
    names = TRAFFIC_COUNTERS + ROUTING_COUNTERS
    with span("sim.run", **args) as sp:
        if tracer is None:
            yield
            return
        before = [tracer.counter(c) for c in names]
        yield
        sp.set(**{c.split(".", 1)[1]: tracer.counter(c) - b
                  for c, b in zip(names, before)})


def traced_jit_call(sp, jitted, *args):
    """Call the `jax.jit` function `jitted` inside the open span `sp`.

    While a tracer is installed, whether the call compiled is read from
    the function's own executable cache, so a retrace for new shapes or
    shardings is caught as well as the first call: `sp` gets
    `jit_compile`, and `sim.jit_compiles` counts each one. With a
    syncing tracer the call is waited for, so the span's wall holds the
    device time.
    """
    if not obs_enabled():
        return jitted(*args)
    before = jitted._cache_size()
    out = jitted(*args)
    if syncing():
        jax.block_until_ready(out)   # honest walls; values untouched
    compiled = jitted._cache_size() > before
    sp.set(jit_compile=compiled)
    if compiled:
        count("sim.jit_compiles")
    return out


def sync_round_metrics(plans, t_start: float, t_end: float) -> dict:
    """Per-satellite round metrics from a synchronous round's ClientPlans —
    the kwargs `_finish_round` consumes. Shared by `_run_sync` and the
    batched scenario planner so record arithmetic stays bitwise-identical."""
    return dict(
        t_start=t_start, t_end=t_end,
        participants=[p.k for p in plans],
        epochs=[p.epochs for p in plans],
        idle_s=[max(0.0, (t_end - t_start)
                    - (p.rx_end - p.rx_start)
                    - (p.train_end - p.train_start)
                    - (p.tx_end - p.tx_start)) for p in plans],
        compute_s=[p.train_end - p.train_start for p in plans],
        comm_s=[(p.rx_end - p.rx_start)
                + (p.tx_end - p.tx_start) for p in plans],
        relays=[p.relay for p in plans],
        staleness=[0] * len(plans),
        relay_hops=[p.isl_hops for p in plans],
        comms_bytes=[p.comm_bytes for p in plans],
    )


def buffer_weights(ns: np.ndarray, staleness: np.ndarray,
                   max_staleness: int) -> np.ndarray:
    """FedBuff admission: updates staler than the bound get zero weight.

    `ns` are the raw aggregation weights (client sample counts), `staleness`
    the global-version lag of each buffered update.
    """
    return admission_weights(ns, staleness, max_staleness)


def prune_history(history: dict, outstanding: Iterable[int],
                  version: int) -> None:
    """Drop global-model versions no in-flight client still anchors on.

    `outstanding` holds the download versions of every in-flight client;
    versions >= min(outstanding) must survive (they are future proximal
    anchors). With nothing in flight only the current `version` is kept.
    Mutates `history` in place.
    """
    keep_from = min(outstanding, default=version)
    for v in list(history):
        if v < keep_from:
            del history[v]


class ConstellationSim:
    """Run one (constellation x network x algorithm x workload) scenario."""

    def __init__(
        self,
        constellation: WalkerStar,
        stations,
        algorithm: SpaceifiedAlgorithm,
        data: FederatedDataset | None = None,
        hw: HardwareModel | None = None,
        cfg: SimConfig | None = None,
        access: AccessWindows | None = None,
        contact_plan: ContactPlan | None = None,
        link_model: LinkModel | None = None,
        isl_link: LinkModel | None = None,
        isl_topology: ISLTopology | None = None,
        workload: Workload | str | None = None,
        execution: str | None = None,
        apply_fn=femnist_mlp_apply,
        init_fn=femnist_mlp_init,
    ):
        self.constellation = constellation
        self.stations = stations
        self.alg = algorithm
        self.cfg = cfg or SimConfig()
        # Workload resolution. Passing `workload` is the first-class path;
        # the `apply_fn`/`init_fn` kwargs keep the seed's FEMNIST-shaped
        # contract working unchanged (classification loss + accuracy eval,
        # paper-constant hardware).
        if workload is not None:
            self.workload = get_workload(workload)
        else:
            from repro.core.workload import classification_workload
            self.workload = classification_workload(
                "custom_classifier", init_fn, apply_fn,
                model_bytes_override=C.MODEL_BYTES,
                epoch_mflops_override=C.EPOCH_MFLOPS)
        # Hardware: explicit > workload-derived > paper constants. The
        # `femnist_mlp` workload's pinned cost makes all three identical
        # on the default path.
        if hw is not None:
            self.hw = hw
        elif workload is not None:
            self.hw = HardwareModel.for_workload(self.workload)
        else:
            self.hw = HardwareModel()
        # Uplink transfer codec: the algorithm's validated knob resolves
        # to a registry codec and rides inside the HardwareModel so every
        # wire-pricing consumer (selection, async feed, batched planner)
        # prices encoded uplinks. "identity" leaves the HardwareModel
        # untouched — the seed's exact pricing path, bit for bit. A
        # caller-supplied `hw` that already carries a codec keeps it
        # unless the algorithm names a lossy one.
        from repro.comms.codec import get_codec
        self.codec = get_codec(getattr(algorithm, "codec", "identity"))
        if self.codec.name != "identity":
            self.hw = dataclasses.replace(
                self.hw, codec=self.codec,
                bytes_per_param=int(self.workload.bytes_per_param))
        elif self.hw.codec is not None:
            self.codec = self.hw.codec
        self._codec_fns: dict[bool, object] = {}
        self.data = data
        self.init_fn = self.workload.init_fn
        if access is not None:
            self.aw = access
        else:
            with span("sim.access_windows", sats=constellation.n_sats):
                self.aw = compute_access_windows(
                    constellation, stations, horizon_s=self.cfg.horizon_s)
        # Comms: algorithms marked `isl=True` (or an explicit link model)
        # plan against a ContactPlan; everything else keeps the seed's
        # AccessWindows-only path, bit for bit.
        self.plan = contact_plan
        if self.plan is not None and (link_model is not None
                                      or isl_link is not None):
            # A cached plan is geometry, not pricing: re-rate it with the
            # requested link models (zero re-propagation; a LinkBudget
            # needs the plan's cached slant ranges). `rerate` semantics:
            # a lone link_model prices both sides (one-radio default); a
            # lone isl_link re-prices ISLs and keeps the plan's ground
            # pricing verbatim.
            self.plan = self.plan.rerate(link_model, isl_link)
        elif self.plan is None and (algorithm.isl or link_model is not None):
            ground = link_model or ConstantRate(self.hw.link_mbps)
            iw = None
            if algorithm.isl:
                topo = isl_topology or ISLTopology.walker_star(constellation)
                iw = compute_isl_windows(constellation, topo,
                                         horizon_s=self.cfg.horizon_s)
            self.plan = build_contact_plan(
                self.aw, iw, ground, isl_link or ground,
                constellation=constellation, stations=stations)
        # Execution mode: per-run override > workload capability. One
        # validator (shared with Workload.with_execution) owns the
        # accepted set, so the two entry points cannot drift.
        self.execution = validate_execution(
            execution or self.workload.execution)
        if self.execution == "mesh":
            # The mesh round step stacks one (x, y) sample stream per pod
            # slot. A workload whose launch-style dict-batch schema
            # declares extra streams (prefix/encoder embeddings) cannot
            # be expressed that way — refuse instead of silently
            # dropping the extra keys.
            dims = self.workload.mesh_batch_dims
            streams = [k for k in (dims or {}) if k != "labels"]
            if len(streams) > 1:
                raise ValueError(
                    f"workload {self.workload.name!r} declares a "
                    f"multi-stream mesh batch schema {sorted(dims)}; the "
                    "engine's mesh path carries a single (x, y) sample "
                    "stream per pod slot — run with execution='host' or "
                    "drive launch.fl_round.make_fl_round_step directly")
            if self.workload.loss_has_aux:
                raise ValueError(
                    f"workload {self.workload.name!r} returns per-step "
                    "statistics with its loss; the mesh round step "
                    "carries parameters only — run with execution='host'")
            # The collective realizes exactly the weighted-average /
            # discounted-delta family; a custom Strategy.aggregate would
            # be silently bypassed, so refuse instead.
            from repro.core.strategies.fedbuff import FedBuffSat
            agg = type(algorithm.strategy).aggregate
            if agg not in (Strategy.aggregate, FedBuffSat.aggregate):
                raise ValueError(
                    f"strategy {algorithm.strategy.name!r} overrides "
                    "aggregate() outside the weighted-average / "
                    "staleness-discounted-delta family; mesh execution "
                    "would bypass it — run with execution='host'")
        self._params_hist: list = []
        self._shards = None           # `resident_shards` during a run
        if self.cfg.train:
            if self.data is None:
                self.data = self.workload.make_data(constellation.n_sats,
                                                    seed=self.cfg.seed)
            assert self.data.n_clients == constellation.n_sats
            # Jitted updaters are built lazily per power-of-two step bound so
            # a 45-step FedAvg round never pays for the 128-step worst case.
            self._updaters: dict[tuple, object] = {}
            self._agg = None          # `_aggregator`, built on first use
            # Mesh-path caches: one client mesh per pod-axis size, one
            # jitted collective round step per (step bound, axis size).
            self._meshes: dict[int, object] = {}
            self._mesh_steps: dict[tuple[int, int], object] = {}

    def _updater(self, bound: int, anchored: bool):
        key = (bound, anchored)
        if key not in self._updaters:
            self._updaters[key] = jax.jit(vmapped_client_update(
                self.workload.loss_fn, lr=self.cfg.lr,
                batch_size=self.cfg.batch_size, max_steps=bound,
                anchored=anchored, has_aux=self.workload.loss_has_aux))
        return self._updaters[key]

    def _aggregator(self):
        """Jitted: the strategy's `aggregate`, one program per client
        count. Traced for this sim alone, so the aggregation functions
        are read as they stand when it first aggregates. It wraps a
        fresh function: jit keys a bound method's trace by equality, and
        sims share their strategy."""
        if self._agg is None:
            strategy = self.alg.strategy
            self._agg = jax.jit(lambda *args: strategy.aggregate(*args))
        return self._agg

    def _start(self, params, x, y, stacked=None):
        """The vmapped update's start state for the clients with shards
        `x`, `y`: `stacked` (their own start models) or else `params`
        broadcast to each, with zero stats beside it where the workload's
        loss returns stats."""
        k = x.shape[0]
        if stacked is None:
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (k,) + a.shape), params)
        if not self.workload.loss_has_aux:
            return stacked
        zeros = zero_stats(self.workload.loss_fn, params, x[0], y[0],
                           self.cfg.batch_size)
        return stacked, jax.tree.map(
            lambda z: jnp.broadcast_to(z, (k,) + z.shape), zeros)

    def _folder(self, bound: int):
        """Jitted: a round's clients, each trained from `params` as the
        vmapped update trains it, one after another (the TPU's grouped
        matmul takes no batch axis), each weighted return added to the
        accumulator as it lands; the accumulator is donated. The local
        steps are unrolled, so the parameters are updated in place.
        (acc, params, x, y, n, steps, w, prox_mu, rngs) -> (acc, the
        clients' stats summed)."""
        key = (bound, "fold")
        if key not in self._updaters:
            wl, cfg = self.workload, self.cfg
            update = make_client_update(
                loss_fn=wl.loss_fn, lr=cfg.lr, batch_size=cfg.batch_size,
                max_steps=bound, has_aux=wl.loss_has_aux, unroll=True)

            def fold(acc, params, x, y, n, steps, w, prox_mu, rngs):
                stats = {}
                if wl.loss_has_aux:
                    stats = zero_stats(wl.loss_fn, params, x[0], y[0],
                                       cfg.batch_size)

                def client(carry, row):
                    acc, stats = carry
                    xk, yk, nk, sk, wk, rk = row
                    start = (params, stats) if wl.loss_has_aux else params
                    out = update(start, params, xk, yk, nk, sk, prox_mu, rk)
                    out, stats = out if wl.loss_has_aux else (out, stats)
                    acc = fold_weighted(
                        acc, jax.tree.map(lambda a: a[None], out), wk[None])
                    return (acc, stats), None

                (acc, stats), _ = jax.lax.scan(
                    client, (acc, stats), (x, y, n, steps, w, rngs))
                return acc, stats

            self._updaters[key] = jax.jit(fold, donate_argnums=0)
        return self._updaters[key]

    def _client_mesh(self, n_clients: int):
        from repro.sharding.flmesh import client_mesh
        size = max(1, min(len(jax.devices()), n_clients))
        if size not in self._meshes:
            self._meshes[size] = client_mesh(
                size, axis=self.workload.mesh_axis)
        return self._meshes[size]

    def _mesh_step(self, bound: int, mesh):
        from repro.launch.fl_round import make_mesh_round_step
        key = (bound, int(mesh.shape[self.workload.mesh_axis]))
        if key not in self._mesh_steps:
            self._mesh_steps[key] = jax.jit(make_mesh_round_step(
                self.workload.loss_fn, mesh, lr=self.cfg.lr,
                batch_size=self.cfg.batch_size, max_steps=bound,
                server_lr=getattr(self.alg.strategy, "server_lr", 1.0),
                axis=self.workload.mesh_axis,
                codec=self.codec if self.codec.lossy else None))
        return self._mesh_steps[key]

    @staticmethod
    def _bound(steps: np.ndarray | list[int]) -> int:
        m = max(int(np.max(steps)), 1)
        return 1 << (m - 1).bit_length()

    # ------------------------------------------------------------------ #
    def run(self) -> SimResult:
        K = self.constellation.n_sats
        with run_span(execution=self.execution, sats=K):
            if K < 2:
                # A single satellite cannot federate (heatmap top-left = 0).
                return self._result([], [], None)
            try:
                return self._run_events()
            finally:
                self._shards = None   # resident for this run only

    # ------------------------------------------------------------------ #
    def _steps_for(self, k: int, epochs: int) -> int:
        n_k = int(self.data.n[k]) if self.data is not None else 256
        return client_steps(n_k, epochs, self.cfg.batch_size,
                            self.cfg.max_steps)

    # ------------------------------------------------------------------ #
    # Shared round-execution core (sync barrier AND async buffer flushes)
    # ------------------------------------------------------------------ #
    def _round_inputs(self, ks: list[int], epochs: list[int], rng):
        """The round's local-step counts (uploaded), one key a client and
        the power-of-two step bound. The run's training shards go up to
        the device at its first trained round (`resident_shards`)."""
        steps_np = [self._steps_for(k, e) for k, e in zip(ks, epochs)]
        if self._shards is None:      # the run's first trained round
            self._shards = resident_shards(self.data)
        return (to_device(steps_np, jnp.int32),
                jax.random.split(rng, len(ks)), self._bound(steps_np))

    def _run_clients(self, global_params, ks: list[int], epochs: list[int],
                     rng, anchors=None):
        """Train-batch assembly + vmapped ClientUpdate for `ks`.

        The run's training shards go up to the device at its first trained
        round (`resident_shards`); each round then uploads only the
        participants' row numbers and gathers their shards there.

        `anchors` is None for the synchronous barrier (everyone anchors on
        the current global model, broadcast once) or a stacked pytree of
        per-client anchor versions (FedBuff). Returns the stacked client
        parameter returns.
        """
        steps, rngs, bound = self._round_inputs(ks, epochs, rng)
        x, y, n = self._shards(ks)
        anchored = anchors is not None
        params0 = self._start(global_params, x, y, stacked=anchors)
        if not anchored:
            anchors = global_params
        update = self._updater(bound, anchored=anchored)
        with span("sim.client_train", clients=len(ks),
                  step_bound=bound) as sp:
            out = traced_jit_call(sp, update, params0, anchors, x, y, n,
                                  steps, self.alg.strategy.prox_mu, rngs)
        if self.workload.loss_has_aux:
            out, stats = out
            count_returned(stats)
        return out

    def _stream_clients(self, global_params, ks: list[int],
                        epochs: list[int], rng, weights):
        """A synchronous round whose clients' model copies do not fit the
        device together: one program trains its clients one after
        another, each as `_run_clients` would train it within the whole
        round (the same rows, keys and power-of-two step bound), and
        folds each weighted return into one donated accumulator as it
        lands. The accumulator is then the round's weighted average
        (Eq. 1), `Strategy.aggregate`'s result, so the aggregation runs
        inside the span `sim.client` that times the program (`clients`,
        and `steps`, the local steps it executes)."""
        strategy = self.alg.strategy
        if self.codec.lossy or type(strategy).aggregate is not \
                Strategy.aggregate:
            raise ValueError(
                f"{len(ks)} copies of workload {self.workload.name!r} do "
                "not fit the device together; such rounds are folded one "
                "client at a time for the synchronous weighted average "
                f"alone, not for strategy {strategy.name!r}"
                + (f" with codec {self.codec.name!r}" if self.codec.lossy
                   else ""))
        steps, rngs, bound = self._round_inputs(ks, epochs, rng)
        x, y, n = self._shards(ks)
        acc = jax.tree.map(jnp.zeros_like, global_params)
        with span("sim.client_train", clients=len(ks), step_bound=bound), \
                span("sim.client", clients=len(ks),
                     steps=bound * len(ks)) as sp:
            acc, stats = traced_jit_call(
                sp, self._folder(bound), acc, global_params, x, y, n, steps,
                normalized_weights(weights), strategy.prox_mu, rngs)
        count_returned(stats)
        return acc

    def _run_clients_mesh(self, global_params, ks: list[int],
                          epochs: list[int], rng, *, weights, staleness,
                          anchors=None):
        """Cluster-as-collective round: clients are pod slots on the FL
        mesh; local SGD + aggregation happen in one shard_mapped step
        (`launch.fl_round.make_mesh_round_step`). Returns the *new global
        params* — aggregation is part of the collective.

        Batch assembly mirrors `_run_clients` exactly (same steps, same
        per-client RNG stream), then pads the pod axis to a multiple of
        the mesh axis size with zero-weight/zero-step slots — the dense
        equivalent of an out-of-contact satellite.
        """
        from repro.sharding.flmesh import pad_client_count
        steps_np = [self._steps_for(k, e) for k, e in zip(ks, epochs)]
        mesh = self._client_mesh(len(ks))
        total = pad_client_count(len(ks), mesh, self.workload.mesh_axis)
        pad = total - len(ks)
        ks_p = list(ks) + [ks[0]] * pad      # real rows; steps 0 mask them
        x = to_device(self.data.x[ks_p])
        y = to_device(self.data.y[ks_p])
        n = to_device(self.data.n[ks_p])
        steps = to_device(steps_np + [0] * pad, jnp.int32)
        w = jnp.concatenate([to_device(weights, jnp.float32),
                             jnp.zeros((pad,), jnp.float32)])
        stale = jnp.concatenate([to_device(staleness, jnp.int32),
                                 jnp.zeros((pad,), jnp.int32)])
        rngs = jax.random.split(rng, len(ks))   # identical to the host path
        if pad:
            rngs = jnp.concatenate(
                [rngs, jnp.broadcast_to(rngs[:1], (pad,) + rngs.shape[1:])])
        if anchors is None:                      # sync barrier: broadcast
            anchors = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (total,) + a.shape),
                global_params)
        elif pad:                                # FedBuff: pad with current
            anchors = jax.tree.map(
                lambda s, g: jnp.concatenate(
                    [s, jnp.broadcast_to(g, (pad,) + g.shape)]),
                anchors, global_params)
        bound = self._bound(steps_np)
        step_fn = self._mesh_step(bound, mesh)
        with span("sim.client_train", mode="mesh", clients=len(ks),
                  step_bound=bound) as sp:
            return traced_jit_call(sp, step_fn, global_params, anchors, x, y,
                                   n, steps, w, stale,
                                   self.alg.strategy.prox_mu, rngs)

    def _codec_roundtrip(self, anchored: bool):
        """Jitted vmapped encode/decode of the stacked client returns.

        Each client's return is re-expressed as anchor + codec.apply(delta)
        — exactly what the server receives after a lossy uplink. Cached
        per anchor layout (broadcast global vs stacked per-client)."""
        from repro.comms.codec import client_roundtrip
        if anchored not in self._codec_fns:
            self._codec_fns[anchored] = jax.jit(jax.vmap(
                client_roundtrip(self.codec),
                in_axes=(0, 0 if anchored else None, 0)))
        return self._codec_fns[anchored]

    def _train_round(self, global_params, ks: list[int], epochs: list[int],
                     rng, *, weights, staleness, anchors=None):
        """Client updates + aggregation for one round (or buffer flush),
        dispatched on the execution mode. Returns the new global params."""
        if self.execution == "mesh":
            return self._run_clients_mesh(
                global_params, ks, epochs, rng, weights=weights,
                staleness=staleness, anchors=anchors)
        if not copies_fit(global_params, len(ks), device_bytes_limit()):
            if anchors is not None:
                raise ValueError(
                    f"{len(ks)} copies of workload {self.workload.name!r} "
                    "do not fit the device together; buffered rounds "
                    "anchored on older versions are not streamed")
            return self._stream_clients(global_params, ks, epochs, rng,
                                        weights)
        stacked = self._run_clients(global_params, ks, epochs, rng,
                                    anchors=anchors)
        if self.codec.lossy:
            # The server only ever sees the codec round-trip of each
            # client's delta — same per-client RNG stream as the updater
            # (split(rng, len(ks)); the codec folds in its own tag), so
            # host / mesh / batched paths share the codec randomness.
            anchored = anchors is not None
            rngs = jax.random.split(rng, len(ks))
            rt = self._codec_roundtrip(anchored)
            decoded = rt(stacked, anchors if anchored else global_params,
                         rngs)
            if syncing():
                err = sum(jnp.sum((a - b) ** 2)
                          for a, b in zip(jax.tree.leaves(stacked),
                                          jax.tree.leaves(decoded)))
                count("comms.codec_error", float(np.sqrt(to_host(err))))
            stacked = decoded
        with span("sim.aggregate", strategy=self.alg.strategy.name,
                  clients=len(ks)) as sp:
            return traced_jit_call(sp, self._aggregator(), global_params,
                                   stacked, to_device(weights),
                                   to_device(staleness))

    def _finish_round(self, rounds: list[RoundRecord], curve: list,
                      global_params, *, t_start: float, t_end: float,
                      participants, epochs, idle_s, compute_s, comm_s,
                      relays, staleness, relay_hops, comms_bytes,
                      do_eval: bool) -> RoundRecord:
        """Construct the RoundRecord, run the eval slot, and append.

        `do_eval` is the eval *cadence* (this round hits the eval slot);
        accuracy is only computed when the run trains."""
        # Wire savings vs full-precision returns over the same legs:
        # (1 + hops) * model_bytes uplink + model_bytes download, minus
        # what was actually billed. IEEE-exact 0.0 for the identity codec
        # (every term is the same sum of model_bytes).
        mb = float(self.hw.model_bytes)
        wire_saved = sum((1.0 + h) * mb + mb - cb
                         for h, cb in zip(relay_hops, comms_bytes))
        if obs_enabled():
            # Encoded uplink bytes actually on the wire this round
            # (billed bytes minus the full-precision download leg).
            count("comms.encoded_bytes", sum(cb - mb for cb in comms_bytes))
        rec = RoundRecord(
            idx=len(rounds), t_start=t_start, t_end=t_end,
            participants=participants, epochs=epochs, idle_s=idle_s,
            compute_s=compute_s, comm_s=comm_s, relays=relays,
            staleness=staleness, relay_hops=relay_hops,
            comms_bytes=comms_bytes, wire_bytes_saved=wire_saved,
            execution=self.execution,
        )
        if self.cfg.record_params and global_params is not None:
            self._params_hist.append(to_host(global_params))
        if do_eval:
            # The eval slot exists in the round protocol whether or not
            # this run trains; timing-only sweeps record it as an empty
            # span (trained=False) so traces show the full phase chain.
            with span("sim.eval", round=rec.idx, trained=self.cfg.train):
                if self.cfg.train:
                    rec.accuracy = self._eval(global_params, t_end)
                    curve.append((rec.idx, t_end, rec.accuracy))
                count("sim.evals")
        rounds.append(rec)
        count("sim.rounds")
        return rec

    def _final_eval(self, rounds: list[RoundRecord], curve: list,
                    global_params) -> None:
        """Evaluate the final model when a run exits off-cadence.

        The round loops only hit the eval slot on the cadence (or, for the
        sync barrier, on the max_rounds-th round), so a run truncated by
        the horizon, an empty selection, or a drained event heap used to
        end its accuracy curve rounds before the final aggregation. Called
        on every exit path so `curve[-1]` always reflects `final_params`.
        """
        if not (self.cfg.train and rounds):
            return
        last = rounds[-1]
        if curve and curve[-1][0] == last.idx:
            return  # the cadence already evaluated the final model
        with span("sim.eval", round=last.idx, trained=True,
                  exit_path=True):
            last.accuracy = self._eval(global_params, last.t_end)
            curve.append((last.idx, last.t_end, last.accuracy))
            count("sim.evals")

    def _result(self, rounds: list[RoundRecord], curve: list,
                global_params) -> SimResult:
        final = (to_host(global_params)
                 if (self.cfg.train and global_params is not None) else None)
        return SimResult(self.alg.name, self.constellation.n_sats,
                         len(self.stations), rounds, curve,
                         execution=self.execution,
                         params_history=self._params_hist,
                         final_params=final)

    def _eval(self, global_params, t: float) -> float:
        """Evaluation-stage client selection: same contact protocol.

        The eval batch is padded to the next power-of-two client count
        (`_bound` idiom) with zero-weight rows, so the workload's eval_fn
        — jitted on the stacked shape — retraces per bucket instead of
        per distinct participant count.
        """
        c = min(self.cfg.clients_per_round, self.constellation.n_sats)
        with span("sim.select", stage="eval"):
            plans = self.alg.selector.select(
                self.aw, t, range(self.constellation.n_sats), c,
                self.alg.strategy, self.hw, self.alg.local_epochs,
                self.alg.min_epochs, plan=self.plan)
        ks = [p.k for p in plans] or list(range(min(c, self.data.n_clients)))
        pad = self._bound([len(ks)]) - len(ks)
        ks_p = ks + [ks[0]] * pad
        n_eval = np.asarray(self.data.n_eval[ks_p]).copy()
        if pad:
            n_eval[len(ks):] = 0  # masked out of the weighted accuracy
        acc = self.workload.eval_fn(global_params,
                                    to_device(self.data.x_eval[ks_p]),
                                    to_device(self.data.y_eval[ks_p]),
                                    to_device(n_eval))
        return float(to_host(acc))

    # ------------------------------------------------------------------ #
    # Strategy-driven event loop
    # ------------------------------------------------------------------ #
    def _build_outlook(self) -> ContactOutlook:
        """Read-only contact-schedule view handed to the strategy hooks.

        Built from the compiled ContactPlan when the algorithm plans
        against one, otherwise straight from the access windows at the
        hardware link rate. Only constructed when a hook actually reads
        it (`_LazyOutlook`), so stock strategies pay nothing."""
        if self.plan is not None:
            return ContactOutlook.from_plan(self.plan)
        return ContactOutlook.from_access(
            self.aw, rate_bps=self.hw.link_mbps * 1e6)

    def _sync_flush_groups(self, plans, outlook) -> list[list[int]]:
        """Partition one synchronous selection into aggregation groups.

        Scheduled returns are fed through `admit`/`should_flush` in
        arrival (tx_end) order; each positive flush decision closes a
        group. Group members are emitted in plan (selection) order, so
        aggregation weight order matches the classic barrier bitwise.
        The default hooks accept everything and only flush a full
        buffer, which reproduces the single all-plans barrier exactly;
        per-visit strategies (ground-assisted) close a group at every
        station-visit boundary instead."""
        strategy = self.alg.strategy
        order = sorted(range(len(plans)), key=lambda i: plans[i].tx_end)
        groups: list[list[int]] = []
        pend_idx: list[int] = []
        pend_upd: list[PendingUpdate] = []
        for pos, i in enumerate(order):
            p = plans[i]
            nxt = (plans[order[pos + 1]].tx_end
                   if pos + 1 < len(order) else None)
            upd = PendingUpdate(k=p.k, staleness=0, epochs=p.epochs,
                                tx_end=p.tx_end)
            if not strategy.admit(upd, BufferState(
                    updates=tuple(pend_upd), target_size=len(plans),
                    now=p.tx_end, next_arrival_s=nxt)):
                continue      # rejected sync returns are dropped
            pend_idx.append(i)
            pend_upd.append(upd)
            state = BufferState(updates=tuple(pend_upd),
                                target_size=len(plans), now=p.tx_end,
                                next_arrival_s=nxt)
            if strategy.should_flush(state, outlook):
                groups.append(sorted(pend_idx))
                pend_idx, pend_upd = [], []
        if pend_idx:      # the tail aggregates rather than being dropped
            groups.append(sorted(pend_idx))
        return groups

    def _run_events(self) -> SimResult:
        """The unified round loop: one of two event feeds (synchronous
        selection barrier / asynchronous upload heap) routes every
        scheduling decision through the strategy hooks."""
        cfg, alg = self.cfg, self.alg
        rng = jax.random.PRNGKey(cfg.seed)
        rng, init_rng = jax.random.split(rng)
        global_params = self.init_fn(init_rng) if cfg.train else None
        outlook = _LazyOutlook(self._build_outlook)
        rounds: list[RoundRecord] = []
        curve: list[tuple[int, float, float]] = []
        if alg.synchronous:
            global_params = self._sync_feed(rng, global_params, outlook,
                                            rounds, curve)
        else:
            global_params = self._async_feed(rng, global_params, outlook,
                                             rounds, curve)
        self._final_eval(rounds, curve, global_params)
        return self._result(rounds, curve, global_params)

    def _sync_feed(self, rng, global_params, outlook, rounds, curve):
        """Synchronous feed (Algorithms 1-2): select, then aggregate each
        flush group the strategy closes over the selection's returns."""
        cfg, hw, alg = self.cfg, self.hw, self.alg
        strategy = alg.strategy
        K = self.constellation.n_sats
        c = min(cfg.clients_per_round, K)

        t = 0.0
        stop = False
        while len(rounds) < cfg.max_rounds and not stop:
            t = max(t, strategy.next_sync_point(outlook, t))
            if t >= cfg.horizon_s:
                break
            with span("sim.round", idx=len(rounds)) as round_span:
                with span("sim.select", stage="train"):
                    plans = alg.selector.select(
                        self.aw, t, range(K), c, strategy, hw,
                        alg.local_epochs, alg.min_epochs, plan=self.plan)
                if not plans:
                    round_span.set(aborted="no_plans")
                    break
                groups = self._sync_flush_groups(plans, outlook)
                if not groups:
                    # Strategy admitted nothing: time cannot advance, so
                    # bail out instead of re-selecting the same plans.
                    round_span.set(aborted="no_admits")
                    break
                t_group = t
                for g in groups:
                    if len(rounds) >= cfg.max_rounds:
                        break
                    sub = [plans[i] for i in g]
                    t_end = max(p.tx_end for p in sub)
                    if t_end > cfg.horizon_s:
                        round_span.set(aborted="horizon")
                        stop = True
                        break
                    if cfg.train:
                        rng, sub_rng = jax.random.split(rng)
                        ks = [p.k for p in sub]
                        global_params = self._train_round(
                            global_params, ks, [p.epochs for p in sub],
                            sub_rng,
                            weights=to_device(self.data.n[ks],
                                              jnp.float32),
                            staleness=jnp.zeros((len(sub),), jnp.int32))
                    self._finish_round(
                        rounds, curve, global_params,
                        do_eval=(len(rounds) % cfg.eval_every == 0
                                 or len(rounds) == cfg.max_rounds - 1),
                        **sync_round_metrics(sub, t_group, t_end),
                    )
                    t_group = t_end
                    t = max(t, t_end)
        return global_params

    def _async_feed(self, rng, global_params, outlook, rounds, curve):
        """Asynchronous feed (Algorithm 3): every satellite cycles
        contact->train->upload; the strategy decides which uploads buffer
        and when the buffer flushes (default: at D updates, FedBuff)."""
        cfg, hw, alg = self.cfg, self.hw, self.alg
        strategy = alg.strategy
        K = self.constellation.n_sats
        c = strategy.round_size(min(cfg.clients_per_round, K))
        D = max(1, int(round(alg.buffer_frac * c)))
        history = {0: global_params}
        version = 0
        last_agg_t = 0.0

        # Event heap of (upload_done_t, sat, version_at_download, epochs,
        # download_t, train_span, comm_s).
        heap: list = []

        def schedule_cycle(k: int, t: float, ver: int):
            w = self.aw.next_window(k, t)
            if w is None:
                return
            rx_end = w[0] + hw.tx_time_s
            # Train across the inter-pass gap; upload at the *next* pass
            # (never the download pass itself).
            nxt = self.aw.next_window(k, w[1] + 1.0)
            if nxt is None:
                return
            epochs = max(1, hw.epochs_between(rx_end, nxt[0]))
            train_span = nxt[0] - rx_end   # continuous on-board training
            # Full-precision download leg + codec-priced upload leg
            # (`ul_time_s` IS `tx_time_s` for the identity codec).
            tx_end = nxt[0] + hw.ul_time_s
            heapq.heappush(heap, (tx_end, k, ver, epochs, w[0], train_span,
                                  hw.tx_time_s + hw.ul_time_s))

        for k in range(K):
            schedule_cycle(k, 0.0, 0)

        buffer: list = []
        pending: list[PendingUpdate] = []   # strategy-facing twin of buffer
        while heap and len(rounds) < cfg.max_rounds:
            tx_end, k, ver, epochs, dl_t, train_span, comm_s = heapq.heappop(heap)
            if tx_end > cfg.horizon_s:
                break
            nxt_arrival = heap[0][0] if heap else None
            upd = PendingUpdate(k=k, staleness=version - ver, epochs=epochs,
                                tx_end=tx_end, version=ver)
            if strategy.admit(upd, BufferState(
                    updates=tuple(pending), target_size=D, now=tx_end,
                    version=version, next_arrival_s=nxt_arrival)):
                buffer.append((k, ver, epochs, dl_t, train_span, comm_s,
                               tx_end))
                pending.append(upd)

            state = BufferState(updates=tuple(pending), target_size=D,
                                now=tx_end, version=version,
                                next_arrival_s=nxt_arrival)
            if not buffer or not strategy.should_flush(state, outlook):
                # Satellite immediately re-downloads in the same pass and
                # keeps training — FedBuff's no-idle property (Figure 9c).
                schedule_cycle(k, tx_end, version)
                continue

            # --- aggregate the buffer ---------------------------------- #
            with span("sim.round", idx=len(rounds), mode="async",
                      flush=len(buffer)):
                t_agg = tx_end
                staleness = np.array([version - b[1] for b in buffer],
                                     np.int32)
                ns = np.array([float(self.data.n[b[0]]) if cfg.train else 1.0
                               for b in buffer], np.float32)
                weights = buffer_weights(ns, staleness,
                                         alg.strategy.max_staleness)
                if cfg.train:
                    ks = [b[0] for b in buffer]
                    anchors = jax.tree.map(
                        lambda *xs: jnp.stack(xs),
                        *[history[b[1]] for b in buffer])
                    rng, sub = jax.random.split(rng)
                    global_params = self._train_round(
                        global_params, ks, [b[2] for b in buffer], sub,
                        weights=weights, staleness=staleness,
                        anchors=anchors)
                version += 1
                history[version] = global_params
                # The buffer-filling satellite re-downloads the *new* model.
                schedule_cycle(k, tx_end, version)
                # Prune history entries no in-flight client still anchors on.
                prune_history(history, (e[2] for e in heap), version)

                self._finish_round(
                    rounds, curve, global_params,
                    t_start=last_agg_t, t_end=t_agg,
                    participants=[b[0] for b in buffer],
                    epochs=[b[2] for b in buffer],
                    # Async clients only idle while a pass is out of reach
                    # after the duty-cycle cap ends; within the buffer span
                    # their time is train_span + comms.
                    idle_s=[max(0.0, (b[6] - b[3]) - b[4] - b[5])
                            for b in buffer],
                    compute_s=[b[4] for b in buffer],
                    comm_s=[b[5] for b in buffer],
                    relays=[-1] * len(buffer),
                    staleness=staleness.tolist(),
                    relay_hops=[0] * len(buffer),
                    comms_bytes=[hw.round_trip_bytes] * len(buffer),
                    do_eval=(len(rounds) % cfg.eval_every == 0),
                )
                last_agg_t = t_agg
                buffer = []
                pending = []
        return global_params


class _LazyOutlook:
    """Deferred `ContactOutlook` construction for the strategy hooks.

    The stock strategies' hooks never read the outlook, so building the
    window tables for every run would be pure overhead; this proxy
    builds the real view on first attribute access and forwards
    everything to it afterwards."""

    def __init__(self, build):
        self._build = build
        self._view = None

    def __getattr__(self, name):
        if self._view is None:
            self._view = self._build()
        return getattr(self._view, name)
