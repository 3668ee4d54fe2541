"""Kernel micro-benchmarks: Pallas (interpret on CPU) vs jnp reference.

On this container the Pallas kernels execute in interpret mode, so the
us_per_call numbers indicate correctness-path overhead only — the TPU
numbers come from the roofline analysis. The ref timings double as the
jnp-path baseline used by the FL simulator.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels.fedagg import fedagg
from repro.kernels.flash_attention import flash_attention
from repro.kernels.prox_sgd import prox_sgd
from repro.kernels.ref import (
    attention_ref,
    fedagg_ref,
    prox_sgd_ref,
    wkv6_ref,
)
from repro.kernels.wkv6 import wkv6


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def run():
    rng = np.random.default_rng(0)
    rows = []
    # fedagg at paper scale: 10 clients x 47,887 params
    x = jnp.asarray(rng.normal(size=(10, 47887)), jnp.float32)
    w = jnp.asarray(rng.random(10), jnp.float32)
    rows.append(("fedagg_ref_us", round(_time(jax.jit(fedagg_ref), x, w), 1),
                 "10x47887"))
    rows.append(("fedagg_pallas_interp_us", round(_time(
        lambda a, b: fedagg(a, b, interpret=True), x, w), 1),
                 "10x47887"))
    # prox_sgd
    p = jnp.asarray(rng.normal(size=47887), jnp.float32)
    g = jnp.asarray(rng.normal(size=47887), jnp.float32)
    ref = jax.jit(lambda a, b, c: prox_sgd_ref(a, b, c, 0.05, 0.1))
    rows.append(("prox_sgd_ref_us", round(_time(ref, p, g, p), 1), "47887"))
    rows.append(("prox_sgd_pallas_interp_us",
                 round(_time(lambda a, b, c: prox_sgd(a, b, c, 0.05, 0.1,
                                                      interpret=True),
                             p, g, p), 1), "47887"))
    # flash attention
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    refa = jax.jit(lambda a, b, c: attention_ref(a, b, c))
    rows.append(("attn_ref_us", round(_time(refa, q, k, k), 1),
                 "B1H4S256D64"))
    rows.append(("attn_pallas_interp_us",
                 round(_time(lambda a, b, c: flash_attention(
                     a, b, c, bq=64, bk=64, interpret=True), q, k, k), 1),
                 "B1H4S256D64"))
    # wkv6
    r = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    lw = -jnp.abs(jnp.asarray(rng.normal(size=(1, 4, 256, 64)),
                              jnp.float32)) * 0.3
    s0 = jnp.zeros((1, 4, 64, 64))
    refw = jax.jit(wkv6_ref)
    rows.append(("wkv6_ref_us", round(_time(refw, r, r, v, lw, s0), 1),
                 "T256K64"))
    rows.append(("wkv6_pallas_interp_us",
                 round(_time(lambda *a: wkv6(*a, interpret=True),
                             r, r, v, lw, s0), 1), "T256K64"))
    return rows


def main(argv=None):
    emit(run())


if __name__ == "__main__":
    main()
