"""Pallas TPU kernel: RWKV6/SSD chunked decayed-outer-product scan.

The MXU-friendly chunk formulation of `models/lm/scan_core.py`: grid
(B, H, nChunks) with the chunk dimension innermost ("arbitrary"); the
(K, V) state lives in VMEM scratch and carries across chunk steps. Per
chunk the kernel does three dense matmuls (inter, intra-out, state carry),
a triangular-ones matmul for the cumulative log decay, and the decayed
intra-chunk scores as an (L, L, K) VPU product reduced over K — decay
products are exp() of differences of cumulative logs, all <= 0, so the
kernel is overflow-free for any chunk.

Strict-past convention (o_t excludes i == t); callers add their diagonal
term (RWKV's u-bonus / SSD's (C.B) x_t) outside — same contract as the
jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, s0_ref, o_ref, sT_ref, s_ref,
                 *, chunk: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)              # (L, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)              # (L, V)
    lw = w_ref[0, 0].astype(jnp.float32)             # (L, K) log decay <= 0

    # Inclusive cumulative log decay as a product with a lower-triangular
    # ones matrix: Mosaic has no cumsum lowering, and HIGHEST keeps the
    # MXU from rounding the log decays to bf16.
    incl = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    logc = jax.lax.dot_general(
        incl.astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (L, K)
    logb = logc - lw                                 # exclusive
    s = s_ref[...]                                   # (K, V)

    # Inter-chunk: queries decayed to the chunk boundary against the state.
    rb = r * jnp.exp(logb)
    o = jax.lax.dot_general(rb, s, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # Intra-chunk strict-lower-triangular attention.
    d = logb[:, None, :] - logc[None, :, :]          # (L, L, K)
    a = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(jnp.minimum(d, 0.0)),
                axis=-1)                             # (L, L)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    a = jnp.where(tri, a, 0.0)
    o = o + jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)

    # State carry.
    total = logc[-1:, :]                             # (1, K)
    kd = k * jnp.exp(total - logc)                   # decay to chunk end
    s_new = s * jnp.exp(total[0])[:, None] + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_ref[...] = s_new

    @pl.when(ci == nc - 1)
    def _emit_state():
        sT_ref[0, 0] = s_new.astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
         s0: jax.Array, *, chunk: int = DEFAULT_CHUNK,
         interpret: bool = False):
    """r/k/logw: (B, H, T, K); v: (B, H, T, V); s0: (B, H, K, V).

    Returns (o: (B, H, T, V), s_final: (B, H, K, V)); strict-past outputs.
    """
    B, H, T, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, "pad T to a chunk multiple"
    nc = T // chunk

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, nc=nc)
    o, sT = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, V), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), r.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, s0)
    return o, sT
