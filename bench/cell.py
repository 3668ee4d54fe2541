"""One benchmark cell: a configuration under a traffic mix, built from the
seed into the program's own entry.

- Loop mixes (`executor` "host" or "mesh") build one `ConstellationSim`;
  the entry is its `run()`.
- The batched mix builds one `BatchedSweep` over its scenarios; the entry
  is its `run()`.

Set-up builds the entry once and calls it once to warm up (the checked
call); the window then calls the same object again and again.
"""
from __future__ import annotations

import dataclasses
import importlib
import time

import numpy as np

from bench.reference import Update, client_steps

TIMING_FIELDS = ("t_start", "t_end", "participants", "epochs", "idle_s",
                 "compute_s", "comm_s", "relays", "staleness", "relay_hops",
                 "comms_bytes")
SEED_MOD = 2 ** 31 - 1      # the program's PRNGKey takes a 32-bit seed


def program_seed(seed: int) -> int:
    return int(seed) % SEED_MOD


def client_data(cfg: dict, mix: dict, n_clients: int, seed: int) -> dict:
    """The clients' shards, from the generator that the configuration
    names (`bench/traffic/<cfg["generator"]>.py`), given the seed, the
    configuration and the mix's `data` block."""
    gen = importlib.import_module("bench.traffic." + cfg["generator"])
    return gen.generate(n_clients, seed, cfg, **mix["data"])


class Cell:
    """Builds and drives the program for one (configuration, mix, seed)."""

    def __init__(self, cfg: dict, mix: dict, seed: int, parts: dict):
        from repro.core import ALGORITHMS
        from repro.data.federated import FederatedDataset
        from repro.orbits import (WalkerStar, compute_access_windows,
                                  station_subnetwork)
        from repro.sim import ConstellationSim, SimConfig

        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.executor = mix["executor"]
        horizon = float(mix["horizon_days"]) * 86400.0
        scen = mix["scenarios"]

        t = time.perf_counter()
        shapes = sorted({(s["clusters"], s["sats"]) for s in scen})
        most = max(s["stations"] for s in scen)
        windows = {cs: compute_access_windows(
            WalkerStar(*cs), station_subnetwork(most), horizon_s=horizon)
            for cs in shapes}
        parts["access_s"] = time.perf_counter() - t

        # One writer per satellite index, so smaller constellations take
        # the first rows of the largest one's data.
        t = time.perf_counter()
        k_max = max(c * s for c, s in shapes)
        full = client_data(cfg, mix, k_max, self.seed)
        self.data = {}
        for c, s in shapes:
            self.data[(c, s)] = {k: v[:c * s] for k, v in full.items()}
        parts["data_s"] = time.perf_counter() - t

        t = time.perf_counter()
        alg = ALGORITHMS[mix["algorithm"]["name"]]
        sim_cfg = SimConfig(
            max_rounds=mix["rounds"], horizon_s=horizon,
            clients_per_round=mix["clients_per_round"],
            batch_size=mix["batch_size"], lr=mix["lr"],
            eval_every=mix["eval_every"], max_steps=mix["max_steps"],
            seed=program_seed(seed))
        self.sims = []
        for s in scen:
            cs = (s["clusters"], s["sats"])
            aw = windows[cs]
            if s["stations"] != most:
                aw = aw.subset(s["stations"])
            execution = "mesh" if self.executor == "mesh" else "host"
            self.sims.append(ConstellationSim(
                WalkerStar(*cs), station_subnetwork(s["stations"]), alg,
                data=FederatedDataset(**self.data[cs]), cfg=sim_cfg,
                access=aw, workload=cfg["workload"], execution=execution))
        if self.executor == "batched":
            from repro.sim import BatchedSweep
            self._sweep = BatchedSweep(self.sims)
        parts["build_s"] = time.perf_counter() - t

    # ----------------------------------------------------------- calls --
    def call(self) -> list:
        """One call of the program's entry: a list of SimResult, at the
        numerics the program chooses itself."""
        if self.executor == "batched":
            return self._sweep.run()
        return [self.sims[0].run()]

    def checked_call(self) -> tuple[list, list[dict]]:
        """The set-up call, through the same entry, keeping what the check
        compares: the global model after each of the first `check_rounds`
        updates of a loop run (the sim's own `record_params` history), or
        the final model of a batched run. Returns (results, {update count:
        params} per scenario)."""
        if self.executor == "batched":
            results = self.call()
            n = self.mix["check_rounds"]
            return results, [{n: r.final_params} for r in results]
        sim = self.sims[0]
        plain = sim.cfg
        sim.cfg = dataclasses.replace(plain, record_params=True)
        try:
            res = self.call()[0]
        finally:
            sim.cfg = plain
        hist = list(res.params_history[:self.mix["check_rounds"]])
        res = dataclasses.replace(res, params_history=[])
        return [res], [{r + 1: p for r, p in enumerate(hist)}]

    # ------------------------------------------------------ accounting --
    @staticmethod
    def updates(results) -> int:
        """Global-model updates (rounds or flushes) in one call."""
        return sum(len(r.rounds) for r in results)

    def step_counts(self, results) -> tuple[int, int]:
        """(useful, executed) local-SGD steps of one call. Executed steps
        count every stacked slot to the power-of-two step bound: padded
        clients, mesh slots and finished scenarios' lanes included."""
        b, cap = self.mix["batch_size"], self.mix["max_steps"]
        per = []
        for sim, res in zip(self.sims, results):
            n = sim.data.n
            per.append([[client_steps(int(n[k]), e, b, cap)
                         for k, e in zip(rec.participants, rec.epochs)]
                        for rec in res.rounds])
        useful = sum(sum(s) for rounds in per for s in rounds)
        if self.executor == "batched":
            width = _pow2(max(len(s) for rounds in per for s in rounds))
            executed = 0
            for r in range(max(len(rounds) for rounds in per)):
                live = [s for rounds in per if r < len(rounds)
                        for s in rounds[r]]
                executed += _pow2(max(live)) * width * len(per)
            return useful, executed
        slots = 1
        if self.executor == "mesh":
            import jax
            slots = len(jax.devices())
        executed = 0
        for s in per[0]:
            pod = min(slots, len(s))
            executed += _pow2(max(s)) * (-(-len(s) // pod) * pod)
        return useful, executed

    # -------------------------------------------------------- schedule --
    def schedule(self, results) -> list[list[Update]]:
        """The first `check_rounds` updates of each scenario, as the
        reference needs them: participants, epochs and staleness from the
        RoundRecords."""
        n = self.mix["check_rounds"]
        return [[Update(clients=tuple(rec.participants),
                        epochs=tuple(rec.epochs),
                        staleness=tuple(rec.staleness))
                 for rec in res.rounds[:n]] for res in results]

    def plan_differs(self, results) -> int:
        """Rounds whose timing fields differ from the host loop's planning
        of the same scenario (batched and mesh mixes; 0 otherwise)."""
        if self.executor == "host":
            return 0
        from repro.sim import ConstellationSim
        bad = 0
        for sim, res in zip(self.sims, results):
            twin = ConstellationSim(
                sim.constellation, sim.stations, sim.alg, data=sim.data,
                cfg=dataclasses.replace(sim.cfg, train=False),
                access=sim.aw, workload=sim.workload, execution="host")
            loop = twin.run()
            bad += abs(len(loop.rounds) - len(res.rounds))
            for a, b in zip(loop.rounds, res.rounds):
                bad += any(getattr(a, f) != getattr(b, f)
                           for f in TIMING_FIELDS)
        return bad


def same_results(a: list, b: list) -> bool:
    """Two calls' outputs agree exactly: records, accuracy and params."""
    import jax
    for ra, rb in zip(a, b, strict=True):
        if len(ra.rounds) != len(rb.rounds):
            return False
        if any(getattr(x, f) != getattr(y, f) for x, y in
               zip(ra.rounds, rb.rounds) for f in TIMING_FIELDS):
            return False
        if ra.accuracy_curve != rb.accuracy_curve:
            return False
        la, lb = jax.tree.leaves(ra.final_params), jax.tree.leaves(
            rb.final_params)
        if len(la) != len(lb) or not all(np.array_equal(x, y)
                                         for x, y in zip(la, lb)):
            return False
    return True


def _pow2(m: int) -> int:
    m = max(int(m), 1)
    return 1 << (m - 1).bit_length()
