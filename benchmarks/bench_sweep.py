"""Paper Table 1: the full 768-configuration sweep (timing metrics).

8 algorithm variants x 16 constellations x 6 station networks = 768
scenarios. Gradient-free (round durations and idle times are orbital
quantities); the training-accuracy slice of the sweep lives in
bench_accuracy.py. Emits one row per scenario + aggregate claims.

`--isl` adds the ISL-on dimension: the `*_intracc_isl` variants, whose
relay hand-offs are routed over real inter-satellite links by
`repro.comms` (relay hops + comms bytes appear in the derived column).
`--link-model budget` re-prices every scenario's cached contact plan
with the FSPL/Shannon `LinkBudget` (per-window slant-range geometry, no
re-propagation) so the sweep quantifies the round-duration cost of
realistic fading links; rows are tagged `sweep+budget/...`.
`--horizon-days` shrinks the scenario for smoke/CI runs; `--smoke`
collapses the grid to one scenario (CI's per-workload guard).
`--trace OUT.json` enables the `repro.obs` tracer for the run and writes
a Chrome/Perfetto-compatible trace (open at https://ui.perfetto.dev)
with nested plan-build/round/eval spans and cache-hit counters; add
`--trace-jsonl OUT.jsonl` for the flat event log. Tracing only observes
wall clocks — the emitted rows are bitwise identical either way.
`--workload` re-prices every scenario with a registry workload's derived
cost model — the LM suite (`lm_tiny`, `lm_moe_tiny`, `lm_rwkv6_tiny`,
`lm_hybrid_tiny`) is where the round-duration vs model-bytes crossover
lives: the MoE workload's FLOPs are priced on activated parameters only
while all experts ride the wire.
`--codec` compresses every client's uplink with a `repro.comms.codec`
transfer codec (quant_int8 / quant_fp8 / topk_sparse): wire bytes and
upload durations shrink per the codec's pricing, and with `--train` the
lossy delta runs on the real training path, so the accuracy column is a
measurement, not a model; rows are tagged `sweep~quant_int8/...`.
"""
from __future__ import annotations

import argparse
import os
import sys

if __package__ in (None, ""):       # `python benchmarks/bench_sweep.py ...`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import (     # noqa: E402
    CLUSTERS,
    HORIZON_S,
    SATS_PER_CLUSTER,
    STATIONS,
    emit,
    run_scenario,
    run_scenarios_batched,
)
from repro.compile_cache import use_compile_cache  # noqa: E402

ALG_SUITE = ("fedavg", "fedavg_sched", "fedavg_intracc",
             "fedprox", "fedprox_sched", "fedprox_sched_v2",
             "fedprox_intracc", "fedbuff")
ISL_SUITE = ("fedavg_intracc_isl", "fedprox_intracc_isl")


def run(rounds: int = 20, quick: bool = False, isl: bool = False,
        horizon_s: float = HORIZON_S, workload: str | None = None,
        train: bool = False, execution: str | None = None,
        link_model: str | None = None, smoke: bool = False,
        batched: bool = False, algorithms: tuple[str, ...] | None = None,
        codec: str | None = None):
    if batched and execution:
        raise ValueError("--batched is its own vmapped executor; "
                         "--execution selects the loop path's")
    if algorithms:
        # Validate the whole list up front: an unknown name must fail
        # here with the registry's vocabulary, not rounds deep into the
        # sweep as a bare KeyError.
        from repro.core import ALGORITHMS, algorithm_names
        unknown = sorted(a for a in algorithms if a not in ALGORITHMS)
        if unknown:
            raise ValueError(
                f"unknown algorithm(s) {unknown}; registered algorithms: "
                f"{algorithm_names()}")
        algs = tuple(algorithms)
    else:
        algs = ALG_SUITE[:4] if quick else ALG_SUITE
        if isl:
            algs = algs + ISL_SUITE
    clusters = (2, 10) if quick else CLUSTERS
    sats = (2, 10) if quick else SATS_PER_CLUSTER
    stations = (1, 13) if quick else STATIONS
    if smoke:
        # Single-scenario smoke (CI's per-workload cost-model guard):
        # one algorithm — plus one ISL variant when --isl is on, so
        # relay feasibility vs model bytes is pinned too — on the 2x2
        # constellation, one station. An explicit --algorithms list is
        # kept whole (CI smokes the named strategies, just on the
        # smallest scenario).
        if not algorithms:
            algs = (algs[:1]
                    + tuple(a for a in algs if a.endswith("_isl"))[:1])
        clusters, sats, stations = (2,), (2,), (1,)
    # Non-default workloads re-price every scenario (model bytes / epoch
    # FLOPs from the workload's derived cost model) and tag the row names.
    wtag = f"/{workload}" if workload else ""
    if link_model and link_model != "constant":
        # Budget pricing changes every row's comms arithmetic: tag the
        # names so the regression gate compares like against like.
        wtag = f"+{link_model}{wtag}"
    if codec and codec != "identity":
        # A lossy uplink codec changes the wire/duration arithmetic (and,
        # with --train, the measured accuracy): tag the rows.
        wtag = f"~{codec}{wtag}"
    else:
        codec = None        # identity IS the default path — same rows
    if execution:
        # The execution axis only changes *how* gradients run (host vmap
        # vs mesh collective); tagging timing-only rows with it would
        # claim measurements that never happened.
        if not train:
            raise ValueError("execution= requires train=True")
        wtag += f"@{execution}"
    grid = [(alg, cl, sp, g) for alg in algs for cl in clusters
            for sp in sats for g in stations]
    cells = [c for c in grid if c[1] * c[2] >= 2]
    if batched:
        # One BatchedSweep over every federating cell: rows are built from
        # the same SimResult fields, so the output diffs 1:1 against the
        # loop path above (durations/idle bitwise for timing-only runs).
        results = dict(zip(cells, run_scenarios_batched(
            cells, rounds=rounds, train=train, horizon_s=horizon_s,
            workload=workload, link_model=link_model, codec=codec)))
    else:
        results = {c: run_scenario(*c, rounds=rounds, horizon_s=horizon_s,
                                   workload=workload, train=train,
                                   execution=execution,
                                   link_model=link_model, codec=codec)
                   for c in cells}
    rows = []
    n_run = n_skip = 0
    for alg, cl, sp, g in grid:
        if cl * sp < 2:
            n_skip += 1   # single satellite cannot federate
            rows.append((f"sweep{wtag}/{alg}/c{cl}s{sp}/g{g}",
                         0, "skip:K<2"))
            continue
        res = results[(alg, cl, sp, g)]
        derived = round(res.mean_idle_per_round_s / 3600, 3)
        if alg.endswith("_isl"):
            derived = (f"idle_h={derived};"
                       f"hops={res.total_relay_hops};"
                       f"mb={round(res.total_comms_bytes / 1e6, 2)}")
        elif codec:
            # Codec rows carry the wire story (and the MEASURED accuracy
            # when training) alongside the duration value.
            derived = (f"idle_h={derived};"
                       f"mb={round(res.total_comms_bytes / 1e6, 2)};"
                       f"saved_mb="
                       f"{round(res.total_wire_bytes_saved / 1e6, 2)}")
            if train:
                derived += f";acc={round(res.final_accuracy, 4)}"
        rows.append((
            f"sweep{wtag}/{alg}/c{cl}s{sp}/g{g}",
            round(res.mean_round_duration_s / 3600, 3),
            derived))
        n_run += 1
    rows.append((f"sweep{wtag}/scenarios_run", n_run, f"skipped={n_skip}"))
    return rows


def main(argv=None):
    from repro.core import workload_names
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="single-scenario smoke: first algorithm on the "
                         "2x2 constellation, 1 station (per-workload CI "
                         "cost-model guard)")
    ap.add_argument("--isl", action="store_true",
                    help="add the ISL-enabled *_intracc_isl variants")
    ap.add_argument("--algorithms", default=None, metavar="A,B,...",
                    help="comma-separated registry algorithm names to "
                         "sweep instead of the built-in suite; unknown "
                         "names error up front listing the registry")
    ap.add_argument("--horizon-days", type=float, default=None,
                    help="override the 90-day scenario (smoke/CI runs)")
    ap.add_argument("--workload", default=None, choices=workload_names(),
                    help="re-price the sweep for a registry workload "
                         "(default: the seed's femnist_mlp constants)")
    ap.add_argument("--train", action="store_true",
                    help="run real gradients (default: timing-only)")
    ap.add_argument("--execution", default=None, choices=("host", "mesh"),
                    help="client-update execution mode for --train runs "
                         "(default: the workload's declared mode)")
    ap.add_argument("--batched", action="store_true",
                    help="run the grid as ONE BatchedSweep (repro.sim."
                         "batched) instead of per-cell sim runs; rows are "
                         "parity-checked against the loop path (timing "
                         "bitwise, --train accuracy within 1e-5)")
    ap.add_argument("--link-model", default=None,
                    choices=("constant", "budget"),
                    help="comms pricing: constant 580 Mbps telemetry "
                         "(default) or the slant-range LinkBudget, "
                         "re-rated from the cached plan geometry")
    from repro.comms.codec import codec_names
    ap.add_argument("--codec", default=None, choices=codec_names(),
                    help="uplink transfer codec (repro.comms.codec): "
                         "prices client returns on the wire and, with "
                         "--train, applies the lossy delta on the real "
                         "training path (measured accuracy cost)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable repro.obs tracing and write a Chrome/"
                         "Perfetto trace.json of the run")
    ap.add_argument("--trace-jsonl", default=None, metavar="OUT.jsonl",
                    help="also write the flat JSONL event log "
                         "(requires --trace)")
    args = ap.parse_args(argv)
    if args.execution and not args.train:
        ap.error("--execution changes how gradients run; pair it with "
                 "--train (a timing-only sweep would mislabel its rows)")
    if args.batched and args.execution:
        ap.error("--batched is its own vmapped executor; --execution "
                 "selects the loop path's (host/mesh)")
    if args.trace_jsonl and not args.trace:
        ap.error("--trace-jsonl requires --trace (one tracer, two views)")
    algorithms = None
    if args.algorithms:
        algorithms = tuple(
            a.strip() for a in args.algorithms.split(",") if a.strip())
        if not algorithms:
            ap.error("--algorithms got an empty list")
        from repro.core import ALGORITHMS, algorithm_names
        unknown = sorted(a for a in algorithms if a not in ALGORITHMS)
        if unknown:
            ap.error(f"unknown algorithm(s) {unknown}; registered "
                     f"algorithms: {algorithm_names()}")
    use_compile_cache()
    horizon_s = (args.horizon_days * 86400.0 if args.horizon_days
                 else HORIZON_S)
    if args.trace:
        from repro import obs
        obs.enable()
    emit(run(rounds=args.rounds, quick=args.quick, isl=args.isl,
             horizon_s=horizon_s, workload=args.workload,
             train=args.train, execution=args.execution,
             link_model=args.link_model, smoke=args.smoke,
             batched=args.batched, algorithms=algorithms,
             codec=args.codec))
    if args.trace:
        summary = obs.metrics_summary()
        obs.write_chrome_trace(args.trace)
        if args.trace_jsonl:
            obs.write_jsonl(args.trace_jsonl)
        # Comment-prefixed so the CSV rows above stay machine-parseable.
        for name, value in sorted(summary["counters"].items()):
            print(f"# obs counter {name}={value}")
        for name, rate in sorted(summary["rates"].items()):
            print(f"# obs rate {name}={rate}")
        print(f"# obs wrote trace to {args.trace}"
              + (f" and {args.trace_jsonl}" if args.trace_jsonl else ""))


if __name__ == "__main__":
    main()
