"""Mini reproduction of the paper's headline result (Figures 6-7).

Runs FedAvg vs FedAvgSch vs FedBuff on the 50-satellite constellation
across a station ladder and prints the months->days scheduling speedup.

  PYTHONPATH=src python examples/constellation_sweep.py [--rounds N]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import use_compile_cache
from repro.core import ALGORITHMS
from repro.orbits import WalkerStar, compute_access_windows, station_subnetwork
from repro.sim import ConstellationSim, SimConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    args = ap.parse_args()
    use_compile_cache()

    c = WalkerStar(clusters=5, sats_per_cluster=10)
    print(f"constellation: {c.n_sats} satellites "
          f"({c.clusters} clusters x {c.sats_per_cluster})")
    print(f"{'stations':>8} | {'alg':>14} | {'round (h)':>9} | "
          f"{'total (days)':>12} | {'idle/round (h)':>14}")
    base_days = {}
    for g in (1, 3, 5, 13):
        st = station_subnetwork(g)
        aw = compute_access_windows(c, st, horizon_s=90 * 86400.0)
        for alg in ("fedavg", "fedavg_sched", "fedbuff"):
            cfg = SimConfig(max_rounds=args.rounds,
                            horizon_s=90 * 86400.0, train=False)
            res = ConstellationSim(c, st, ALGORITHMS[alg], cfg=cfg,
                                   access=aw).run()
            days = res.total_time_s / 86400
            if alg == "fedavg":
                base_days[g] = days
            sp = base_days[g] / max(days, 1e-9)
            print(f"{g:>8} | {alg:>14} | "
                  f"{res.mean_round_duration_s/3600:>9.2f} | "
                  f"{days:>12.2f} | {res.mean_idle_per_round_s/3600:>14.3f}"
                  + (f"   ({sp:.1f}x)" if alg != "fedavg" else ""))


if __name__ == "__main__":
    main()
