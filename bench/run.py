"""The benchmark's command: one run of one cell on the chips of this host.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It loads, warms up, measures whole calls of the program's entry for
`--seconds`, checks what they produced against the plain reference, and
prints one JSON object as the last line of standard output. With
`--trace 0` the metrics are the cell's end-to-end ones; with `--trace 1`
its per-layer ones, from a stretch with the program's spans on and a
profiled stretch with them off. It exits non-zero, and prints no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_spec(args.workload)
    t = time.perf_counter()
    parts = {"start_s": t - T0}
    try:
        harness.devices_for(int(spec["cell"]["chips"]), require_tpu=True)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    parts["device_init_s"] = time.perf_counter() - t
    harness.use_bench_cache()
    result = harness.run_cell(spec, args.seed, args.seconds,
                              bool(args.trace), t0=T0, parts=parts)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
