"""Readings for the limits of `correct`, at a cell's own size.

    python bench/calibrate.py --workload <cell> --seeds 1 2 ... --control-seeds 1 2 3

For each seed it builds the cell, makes the checked call, and prints one
JSON line with the compared numbers of

- `program`: the program against the reference (float32, matmul
  precision "highest");
- `control` (control seeds only): the reference computed in bfloat16, put
  in the program's place;
- `fault.<name>` (control seeds only): the float32 reference with one
  planted fault, put in the program's place (`reference.FAULTS`).

All seeds run in one process. Its runs are not the benchmark's: it
measures no window. Run it on the chip; on another device it refuses,
as the benchmark does.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def leaves(got: dict, ref) -> dict:
    """Per-leaf gaps of the first scenario at each kept update count, so
    that the worst leaf can be named."""
    from bench import check
    init, after = ref
    return {n: check.leaf_gaps(p, after[n - 1], init)
            for n, p in sorted(got.items())}


def readings(spec: dict, seed: int, control: bool,
             faults: bool = True) -> dict:
    """The compared numbers of one seed (see the module docstring); with
    `control`, also the control's and, with `faults`, each fault's."""
    import jax
    import jax.numpy as jnp
    from bench import check, reference
    from bench.cell import Cell, program_seed

    cfg, mix, model = spec["cfg"], spec["mix"], spec["model"]
    cell = Cell(cfg, mix, seed, {})
    results, got = cell.checked_call()
    schedule = cell.schedule(results)
    datas = [dict(s.data.__dict__) for s in cell.sims]
    ps = program_seed(seed)

    def follow(dtype, fault=None):
        return [reference.Reference(model, cfg, mix, data, dtype=dtype,
                                    fault=fault).follow(ps, sched)
                for data, sched in zip(datas, schedule)]

    def compared(kept):
        return check.numbers(kept, refs)

    with jax.default_matmul_precision("highest"):
        refs = follow(jnp.float32)
    row = {"seed": seed, "program": compared(got),
           "leaves": leaves(got[0], refs[0])}
    if control:
        def as_program(runs):
            n = mix["check_rounds"]
            return [{r + 1: p for r, p in enumerate(after)}
                    if mix["executor"] != "batched" else {n: after[n - 1]}
                    for _, after in runs]

        bf16 = as_program(follow(jnp.bfloat16))
        row["control"] = compared(bf16)
        row["control_leaves"] = leaves(bf16[0], refs[0])
        with jax.default_matmul_precision("highest"):
            for fault in reference.FAULTS if faults else ():
                row[f"fault.{fault}"] = compared(
                    as_program(follow(jnp.float32, fault)))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_spec(args.workload)
    try:
        harness.devices_for(int(spec["cell"]["chips"]), require_tpu=True)
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    harness.use_bench_cache()
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        t = time.perf_counter()
        row = readings(spec, seed, seed in args.control_seeds)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
