"""Quickstart: space-ified federated learning in ~30 lines.

Builds a 10-satellite Walker-Star constellation over 3 IGS ground
stations, space-ifies FedAvg, and runs 15 real FL rounds (orbital timing +
actual gradient updates on synthetic-FEMNIST).

  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import use_compile_cache
from repro.core import FedAvgSat, spaceify
from repro.data import synth_femnist
from repro.orbits import WalkerStar, station_subnetwork
from repro.sim import ConstellationSim, SimConfig


def main():
    use_compile_cache()
    constellation = WalkerStar(clusters=2, sats_per_cluster=5)
    stations = station_subnetwork(3)
    algorithm = spaceify(FedAvgSat(), schedule=True)   # + FLSchedule

    data = synth_femnist(constellation.n_sats, seed=0)
    sim = ConstellationSim(
        constellation, stations, algorithm, data=data,
        cfg=SimConfig(max_rounds=15, horizon_s=20 * 86400.0, eval_every=5),
    )
    result = sim.run()

    print(f"algorithm : {result.algorithm}")
    print(f"satellites: {result.n_sats}  stations: {result.n_stations}")
    for r, t, acc in result.accuracy_curve:
        print(f"  round {r:3d}  day {t/86400:5.1f}  accuracy {acc:.3f}")
    s = result.summary()
    print(f"mean round duration: {s['mean_round_duration_h']} h")
    print(f"total sim time     : {s['total_days']} days")


if __name__ == "__main__":
    main()
