"""Chip smoke test: one space-ified federated run, end to end, on a TPU.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the mesh executor only

The scenario is the paper's largest grid point: WalkerStar(10, 10) (100
satellites) over the 13-station IGS network, a few days of orbits, and
real on-board training of the paper's model (`femnist_mlp`, 47k
parameters). Everything runs in this one process, in order:

  device   JAX must find a TPU; otherwise the script exits non-zero.
  host     fedavg_sched (sync barrier) and fedbuff (async flush) through
           `ConstellationSim`; accuracy must rise, and the first round,
           rerun on the host's CPU device, must agree with the chip.
  batched  Table-1 scenarios through `run_batched`: RoundRecords equal to
           the loop path's, accuracy within ACC_TOL.
  mesh     execution="mesh" on the host phase's scenarios, against them.
  kernels  fedagg, prox_sgd, flash_attention and wkv6 natively, against
           `repro.kernels.ref`.

`--chips 4` runs only the mesh executor over four chips (the pod axis
spread over all of them) and the one-chip host path it is compared with.

Access windows and data are computed here from --seed; nothing is read
from disk. Any failed check raises, and the script exits non-zero. Timing
lines start with '#'; the last line of standard output is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import ALGORITHMS  # noqa: E402
from repro.data import synth_femnist  # noqa: E402
from repro.orbits import (  # noqa: E402
    WalkerStar,
    compute_access_windows,
    station_subnetwork,
)
from repro.sim import ConstellationSim, SimConfig, run_batched  # noqa: E402

WORKLOAD = "femnist_mlp"
CONSTELLATION = (10, 10)        # clusters x satellites per cluster
STATIONS = 13
HORIZON_S = 4 * 86400.0
ROUNDS = 5
HOST_ALGS = ("fedavg_sched", "fedbuff")
BATCHED_CELLS = [("fedavg", cl, sp, g)
                 for cl, sp in ((2, 5), (5, 5), (10, 10)) for g in (1, 13)]
TIMING_FIELDS = ("t_start", "t_end", "participants", "epochs", "idle_s",
                 "compute_s", "comm_s", "relays", "staleness",
                 "relay_hops", "comms_bytes")

# Tolerances; CHANGES.md gives the reasoning. TPU f32 matmuls run reduced-
# precision (bf16) passes by default, so two paths that order or place
# their matmuls differently drift apart at about 2**-8 per product, and
# local SGD amplifies that: on the CPU alone, a relative nudge of 1e-6 to
# 1e-3 to the initial params moves round-0 params by 2e-2 to 4.9e-2.
PARAM_TOL = 5e-2    # ||a - b|| / ||b - init||: gap over what training moved
ACC_TOL = 0.02      # accuracy gap (13 of 640 eval samples)
ACC_RISE = 0.02     # last evaluation above the first by at least this
KERNEL_TOL = {"fedagg": 1e-5, "prox_sgd": 1e-6,
              "flash_attention": 2e-2, "wkv6": 2e-2}


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


class Scenarios:
    """Access windows and client data per constellation, built once."""

    def __init__(self, seed: int):
        self.seed = seed
        self._aw: dict = {}
        self._data: dict = {}

    def prepare(self, clusters: int, sats: int) -> None:
        key = (clusters, sats)
        if key not in self._aw:
            c = WalkerStar(clusters, sats)
            self._aw[key] = compute_access_windows(
                c, station_subnetwork(STATIONS), horizon_s=HORIZON_S)
            self._data[key] = synth_femnist(c.n_sats, seed=self.seed)

    def sim(self, alg: str, clusters: int, sats: int, n_stations: int, *,
            execution: str | None = None, rounds: int = ROUNDS,
            record_params: bool = False) -> ConstellationSim:
        key = (clusters, sats)
        c = WalkerStar(clusters, sats)
        self.prepare(clusters, sats)
        aw = self._aw[key]
        if n_stations != STATIONS:
            aw = aw.subset(n_stations)
        cfg = SimConfig(max_rounds=rounds, horizon_s=HORIZON_S, eval_every=1,
                        seed=self.seed, record_params=record_params)
        return ConstellationSim(c, station_subnetwork(n_stations),
                                ALGORITHMS[alg], data=self._data[key],
                                cfg=cfg, access=aw, workload=WORKLOAD,
                                execution=execution)


def _flat(tree) -> np.ndarray:
    return np.concatenate([np.ravel(np.asarray(l, np.float64))
                           for l in jax.tree.leaves(tree)])


def param_gap(a, b, init) -> tuple[float, float]:
    """(||a - b|| / ||b - init||, max |a - b|) over whole parameter trees."""
    fa, fb, fi = _flat(a), _flat(b), _flat(init)
    return (float(np.linalg.norm(fa - fb) / np.linalg.norm(fb - fi)),
            float(np.max(np.abs(fa - fb))))


def init_params(seed: int):
    """The engine's round-0 global model for `seed`, on the CPU."""
    from repro.core.workload import get_workload
    with jax.default_device(jax.devices("cpu")[0]):
        _, init_rng = jax.random.split(jax.random.PRNGKey(seed))
        return jax.device_get(get_workload(WORKLOAD).init_fn(init_rng))


def acc_gap(a, b) -> float:
    ca = {i: x for i, _, x in a.accuracy_curve}
    cb = {i: x for i, _, x in b.accuracy_curve}
    check(set(ca) == set(cb), f"eval rounds differ: {sorted(ca)} vs "
                              f"{sorted(cb)}")
    return max(abs(ca[i] - cb[i]) for i in ca)


def records_equal(a, b, what: str) -> None:
    check(len(a.rounds) == len(b.rounds) > 0,
          f"{what}: {len(a.rounds)} vs {len(b.rounds)} rounds")
    for ra, rb in zip(a.rounds, b.rounds):
        for field in TIMING_FIELDS:
            check(getattr(ra, field) == getattr(rb, field),
                  f"{what}: round {ra.idx} field {field} differs")


def timed_run(sim: ConstellationSim):
    """Run a sim traced; returns (result, wall s, cold step s, steady s)."""
    with obs.tracing() as tracer:
        t0 = time.perf_counter()
        res = sim.run()
        wall = time.perf_counter() - t0
    steps = [ev for ev in tracer.events if ev["name"] == "sim.client_train"]
    cold = sum(ev["dur_us"] for ev in steps if ev["args"]["jit_compile"])
    warm = [ev["dur_us"] for ev in steps if not ev["args"]["jit_compile"]]
    return (res, wall, cold / 1e6,
            statistics.median(warm) / 1e6 if warm else float("nan"))


def compare(res, ref, init, what: str) -> None:
    records_equal(res, ref, what)
    gap, gmax = param_gap(res.final_params, ref.final_params, init)
    agap = acc_gap(res, ref)
    log(f"{what}: param gap {gap!r} (max abs {gmax!r}), "
        f"accuracy gap {agap!r}")
    check(gap <= PARAM_TOL, f"{what}: param gap {gap} > {PARAM_TOL}")
    check(agap <= ACC_TOL, f"{what}: accuracy gap {agap} > {ACC_TOL}")


# ----------------------------------------------------------------- phases --
def phase_host(sc: Scenarios, init) -> dict:
    cl, sp = CONSTELLATION
    results = {}
    for alg in HOST_ALGS:
        res, wall, cold, steady = timed_run(
            sc.sim(alg, cl, sp, STATIONS, record_params=True))
        curve = [a for _, _, a in res.accuracy_curve]
        log(f"host {alg}: {res.n_rounds} rounds in {wall!r} s; first "
            f"client step (with compile) {cold!r} s, steady step "
            f"{steady!r} s; accuracy {curve}")
        check(res.n_rounds == ROUNDS, f"host {alg}: {res.n_rounds} rounds")
        check(curve[-1] >= curve[0] + ACC_RISE,
              f"host {alg}: accuracy did not rise ({curve})")
        results[alg] = res
    # The first round again on the host's CPU, same seed and data.
    alg = HOST_ALGS[0]
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = sc.sim(alg, cl, sp, STATIONS, rounds=1).run()
    gap, gmax = param_gap(results[alg].params_history[0], cpu.final_params,
                          init)
    log(f"host {alg} round 0, chip vs CPU: param gap {gap!r} "
        f"(max abs {gmax!r}), accuracy {results[alg].accuracy_curve[0][2]!r}"
        f" vs {cpu.accuracy_curve[0][2]!r}")
    check(gap <= PARAM_TOL, f"chip vs CPU param gap {gap} > {PARAM_TOL}")
    return results


def phase_batched(sc: Scenarios, init) -> None:
    loop = [sc.sim(*cell).run() for cell in BATCHED_CELLS]
    t0 = time.perf_counter()
    batched = run_batched([sc.sim(*cell) for cell in BATCHED_CELLS])
    log(f"batched: {len(BATCHED_CELLS)} scenarios in "
        f"{time.perf_counter() - t0!r} s (with compile)")
    for (alg, cl, sp, g), lr, br in zip(BATCHED_CELLS, loop, batched):
        compare(br, lr, init, f"batched {alg}/c{cl}s{sp}/g{g} vs loop")


def phase_mesh(sc: Scenarios, init, host: dict) -> None:
    cl, sp = CONSTELLATION
    for alg in HOST_ALGS:
        res, wall, cold, steady = timed_run(
            sc.sim(alg, cl, sp, STATIONS, execution="mesh"))
        log(f"mesh {alg}: {wall!r} s; first step (with compile) {cold!r} s,"
            f" steady step {steady!r} s")
        compare(res, host[alg], init, f"mesh {alg} vs host")


def _kernel_err(out, ref) -> float:
    out = np.asarray(jnp.asarray(out, jnp.float32))
    ref = np.asarray(jnp.asarray(ref, jnp.float32))
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


def phase_kernels(seed: int) -> None:
    """Each kernel once, natively, at the shapes tests/test_tpu_compile.py
    compiles, against its jnp oracle (run at full f32 matmul precision)."""
    from repro.kernels import ref
    from repro.kernels.fedagg import fedagg
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.prox_sgd import prox_sgd
    from repro.kernels.wkv6 import wkv6

    rng = np.random.default_rng(seed)

    def normal(shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)

    n_params = _flat(init_params(seed)).size
    x, w = normal((100, n_params)), jnp.asarray(rng.random(100), jnp.float32)
    p, g, p0 = (normal((n_params,)) for _ in range(3))
    q = normal((1, 8, 2048, 256), jnp.bfloat16)
    kv = normal((1, 2, 2048, 256), jnp.bfloat16)
    r, k, v = (normal((1, 32, 2048, 64)) for _ in range(3))
    lw = -jnp.abs(normal((1, 32, 2048, 64))) * 0.3
    s0 = normal((1, 32, 64, 64))
    cases = {
        "fedagg": (lambda: fedagg(x, w), lambda: ref.fedagg_ref(x, w)),
        "prox_sgd": (lambda: prox_sgd(p, g, p0, 0.05, 0.1),
                     lambda: ref.prox_sgd_ref(p, g, p0, 0.05, 0.1)),
        "flash_attention": (lambda: flash_attention(q, kv, kv),
                            lambda: ref.attention_ref(q, kv, kv)),
        "wkv6": (lambda: wkv6(r, k, v, lw, s0),
                 lambda: ref.wkv6_ref(r, k, v, lw, s0)),
    }
    for name, (kernel, oracle) in cases.items():
        t0 = time.perf_counter()
        out = jax.block_until_ready(kernel())
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(kernel())
        steady = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            expect = oracle()
        errs = [_kernel_err(o, e) for o, e in
                zip(jax.tree.leaves(out), jax.tree.leaves(expect))]
        log(f"kernel {name}: first call {first!r} s, second {steady!r} s, "
            f"max error / max |ref| {max(errs)!r}")
        check(max(errs) <= KERNEL_TOL[name],
              f"kernel {name}: error {max(errs)} > {KERNEL_TOL[name]}")


def phase_mesh4(sc: Scenarios, init) -> None:
    """The mesh executor over four chips against the one-chip host path."""
    cl, sp = CONSTELLATION
    for alg in HOST_ALGS:
        host = sc.sim(alg, cl, sp, STATIONS).run()
        sim = sc.sim(alg, cl, sp, STATIONS, execution="mesh")
        res, wall, cold, steady = timed_run(sim)
        log(f"mesh4 {alg}: {wall!r} s; first step (with compile) {cold!r} "
            f"s, steady step {steady!r} s")
        check(set(sim._meshes) == {4},
              f"mesh4 {alg}: pod axis sizes {sorted(sim._meshes)}")
        mesh = sim._meshes[4]
        log(f"mesh4 {alg}: mesh.devices {mesh.devices.tolist()}")
        compare(res, host, init, f"mesh4 {alg} vs one-chip host")
    # Every per-pod operand of the compiled round step is split over the
    # four chips, and the result lands replicated on all of them.
    step = next(iter(sim._mesh_steps.values()))
    n = 12                                   # 10 clients padded to 4 | n
    spec = jax.ShapeDtypeStruct
    params = jax.eval_shape(sim.init_fn, jax.random.PRNGKey(0))
    x, y = sim.data.x, sim.data.y
    per_pod = (jax.tree.map(lambda l: spec((n,) + l.shape, l.dtype), params),
               spec((n,) + x.shape[1:], x.dtype),
               spec((n,) + y.shape[1:], y.dtype), spec((n,), jnp.int32),
               spec((n,), jnp.int32), spec((n,), jnp.float32),
               spec((n,), jnp.int32))
    rngs = spec((n, 2), jnp.uint32)
    compiled = step.lower(params, *per_pod, 0.0, rngs).compile()
    shardings, _ = compiled.input_shardings
    operands = jax.tree.leaves(per_pod) + [rngs]
    placed = jax.tree.leaves(shardings[1:8]) + [shardings[9]]
    for op, s in zip(operands, placed, strict=True):
        check(len(s.device_set) == 4
              and s.shard_shape(op.shape)[0] == n // 4,
              f"mesh4: a per-pod operand {op} is placed as {s}")
    out = jax.tree.leaves(compiled.output_shardings)
    for s in out:
        check(len(s.device_set) == 4, f"mesh4: output placed as {s}")
    log(f"mesh4: {len(operands)} per-pod operands split {n // 4} pods per "
        f"chip over {len(placed[0].device_set)} chips; output on "
        f"{len(out[0].device_set)} chips")


# ------------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh executor over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's devices are "
                 f"{dev.platform}); this script measures only on a TPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU chips, JAX sees {len(devices)}")
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    cache = use_compile_cache()
    log(f"compile cache {cache} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")

    t_all = time.perf_counter()
    sc = Scenarios(args.seed)
    init = init_params(args.seed)
    def setup(shapes):
        for cl, sp in shapes:
            sc.prepare(cl, sp)

    if args.chips == 4:
        phases = [("setup", lambda: setup([CONSTELLATION])),
                  ("mesh4", lambda: phase_mesh4(sc, init))]
    else:
        host: dict = {}
        phases = [
            ("setup", lambda: setup({c[1:3] for c in BATCHED_CELLS})),
            ("host", lambda: host.update(phase_host(sc, init))),
            ("batched", lambda: phase_batched(sc, init)),
            ("mesh", lambda: phase_mesh(sc, init, host)),
            ("kernels", lambda: phase_kernels(args.seed)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        log(f"phase {name}: {time.perf_counter() - t0!r} s")
    log(f"total {time.perf_counter() - t_all!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
