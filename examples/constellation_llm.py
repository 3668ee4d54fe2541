"""End-to-end driver: federate a transformer across a satellite cluster.

The paper's orchestration applied to an assigned LM architecture — now
through the *real* simulation engine: `ConstellationSim` runs the same
event loops, selection protocols, and contact-plan timing as the FEMNIST
experiments, with the LM supplied as a `Workload` (model + next-token
loss + federated token shards + derived cost model). Comms bytes and
epoch times are priced from the reduced architecture's actual parameter
tree via `HardwareModel.for_workload`, so round durations reflect moving
*this* model over the telemetry link.

  PYTHONPATH=src python examples/constellation_llm.py \
      --arch gemma-2b --rounds 6 --alg fedprox
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, lm_arch_ids
from repro.core import ALGORITHMS, lm_workload
from repro.core.timing import HardwareModel
from repro.orbits import WalkerStar, compute_access_windows, station_subnetwork
from repro.sim import ConstellationSim, SimConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=lm_arch_ids())
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--sats", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-steps", type=int, default=16)
    ap.add_argument("--alg", default="fedavg_sched", choices=sorted(ALGORITHMS))
    ap.add_argument("--execution", default=None, choices=("host", "mesh"),
                    help="client-update execution: vmapped host loop or "
                         "cluster-as-collective mesh dispatch")
    args = ap.parse_args()
    use_compile_cache()

    wl = lm_workload(get_config(args.arch).reduced(), seq_len=args.seq,
                     samples_per_client=4 * args.batch)
    hw = HardwareModel.for_workload(wl)
    print(f"federating {wl.name}: {wl.n_params/1e6:.2f}M params "
          f"({wl.model_bytes/1e6:.1f} MB on the wire, "
          f"{hw.tx_time_s:.2f}s per transfer) across {args.sats} satellites")

    # Orbital side: one cluster of `sats` satellites, 3 ground stations.
    c = WalkerStar(clusters=1, sats_per_cluster=args.sats)
    horizon_s = 30 * 86400.0
    aw = compute_access_windows(c, station_subnetwork(3), horizon_s=horizon_s)
    cfg = SimConfig(max_rounds=args.rounds, horizon_s=horizon_s,
                    batch_size=args.batch, lr=args.lr, eval_every=1,
                    max_steps=args.max_steps)
    sim = ConstellationSim(c, station_subnetwork(3), ALGORITHMS[args.alg],
                           workload=wl, hw=hw, cfg=cfg, access=aw,
                           execution=args.execution)
    res = sim.run()

    print(f"execution mode: {res.execution}")
    for rec in res.rounds:
        acc = f"{rec.accuracy:.4f}" if rec.accuracy is not None else "  -   "
        print(f"round {rec.idx}: day {rec.t_end/86400:5.2f}  "
              f"token-acc {acc}  participants {rec.participants}  "
              f"comms {rec.total_comms_bytes/1e6:.1f} MB")
    print(f"{res.n_rounds} rounds in {res.total_time_s/86400:.1f} simulated "
          f"days; best token accuracy {res.max_accuracy:.4f}")


if __name__ == "__main__":
    main()
