"""Per-kernel shape/dtype sweeps, interpret-mode vs ref.py oracles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fedagg import fedagg
from repro.kernels.flash_attention import flash_attention
from repro.kernels.prox_sgd import prox_sgd
from repro.kernels.wkv6 import wkv6
from repro.kernels.ref import (
    attention_ref,
    fedagg_ref,
    prox_sgd_ref,
    wkv6_ref,
)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,p", [(2, 100), (10, 47887), (64, 4096),
                                 (7, 12345)])
def test_fedagg_sweep(k, p, dtype):
    rng = np.random.default_rng(k * p)
    x = _rand(rng, (k, p), dtype)
    w = jnp.asarray(rng.random(k), jnp.float32)
    out = fedagg(x, w, interpret=True)
    ref = fedagg_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("p", [47887, 8192, 130])
def test_prox_sgd_sweep(p, dtype):
    rng = np.random.default_rng(p)
    w, g, w0 = (_rand(rng, (p,), dtype) for _ in range(3))
    out = prox_sgd(w, g, w0, 0.05, 0.1, interpret=True)
    ref = prox_sgd_ref(w, g, w0, 0.05, 0.1)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize(
    "b,h,kv,s,d,causal,window,softcap",
    [
        (1, 2, 2, 128, 64, True, None, None),     # MHA causal
        (2, 4, 2, 128, 32, True, None, None),     # GQA
        (1, 4, 1, 256, 64, True, 64, None),       # MQA + sliding window
        (1, 2, 2, 128, 64, False, None, None),    # bidirectional (encoder)
        (1, 2, 2, 128, 64, True, None, 30.0),     # grok softcap
        (1, 2, 1, 64, 128, True, 16, None),       # window < block
    ])
def test_flash_attention_sweep(b, h, kv, s, d, causal, window, softcap,
                               dtype):
    rng = np.random.default_rng(s + d)
    q = _rand(rng, (b, h, s, d), dtype)
    k = _rand(rng, (b, kv, s, d), dtype)
    v = _rand(rng, (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, bq=32, bk=32, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    q = _rand(rng, (1, 2, 128, 64), jnp.bfloat16)
    k = _rand(rng, (1, 2, 128, 64), jnp.bfloat16)
    v = _rand(rng, (1, 2, 128, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("t,chunk", [(64, 16), (128, 64), (96, 32)])
@pytest.mark.parametrize("kdim,vdim", [(16, 32), (64, 64)])
def test_wkv6_sweep(t, chunk, kdim, vdim):
    rng = np.random.default_rng(t + kdim)
    B, H = 2, 3
    r = _rand(rng, (B, H, t, kdim), jnp.float32)
    k = _rand(rng, (B, H, t, kdim), jnp.float32)
    v = _rand(rng, (B, H, t, vdim), jnp.float32)
    lw = -jnp.abs(_rand(rng, (B, H, t, kdim), jnp.float32)) * 0.3
    s0 = _rand(rng, (B, H, kdim, vdim), jnp.float32)
    o, sT = wkv6(r, k, v, lw, s0, chunk=chunk, interpret=True)
    orf, srf = wkv6_ref(r, k, v, lw, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(srf),
                               rtol=2e-4, atol=2e-4)


def test_wkv6_strong_decay_stability():
    """Long chunks with aggressive decay must not overflow (log-space)."""
    rng = np.random.default_rng(1)
    B, H, T, K, V = 1, 1, 256, 32, 32
    r = _rand(rng, (B, H, T, K), jnp.float32)
    k = _rand(rng, (B, H, T, K), jnp.float32)
    v = _rand(rng, (B, H, T, V), jnp.float32)
    lw = jnp.full((B, H, T, K), -5.0)       # near-total per-step decay
    s0 = jnp.zeros((B, H, K, V))
    o, sT = wkv6(r, k, v, lw, s0, chunk=128, interpret=True)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(sT).all())


def test_fedagg_pytree_roundtrip():
    from repro.kernels.ops import fedagg_pytree
    from repro.core.aggregation import weighted_average
    rng = np.random.default_rng(3)
    tree = {"a": _rand(rng, (4, 3, 5), jnp.float32),
            "b": {"c": _rand(rng, (4, 7), jnp.float32)}}
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    wn = w / w.sum()
    out = fedagg_pytree(tree, wn, interpret=True)
    ref = weighted_average(tree, w)
    for k_, o, r_ in (("a", out["a"], ref["a"]),
                      ("c", out["b"]["c"], ref["b"]["c"])):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r_),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kernel", ["fedagg", "prox_sgd", "flash_attention",
                                    "wkv6"])
def test_kernel_without_interpret_raises_off_tpu(kernel):
    """Interpret mode is opt-in: off a TPU, a native call must fail loudly
    instead of quietly running the kernel body in Python."""
    if jax.default_backend() == "tpu":
        pytest.skip("native kernels run on a TPU")
    x = jnp.ones((2, 1, 64, 32), jnp.float32)
    calls = {
        "fedagg": lambda: fedagg(jnp.ones((2, 100)), jnp.ones((2,))),
        "prox_sgd": lambda: prox_sgd(jnp.ones(130), jnp.ones(130),
                                     jnp.ones(130), 0.05, 0.1),
        "flash_attention": lambda: flash_attention(x, x, x, bq=32, bk=32),
        "wkv6": lambda: wkv6(x, x, x, -jnp.abs(x), jnp.zeros((2, 1, 32, 32)),
                             chunk=32),
    }
    with pytest.raises(ValueError, match="interpret"):
        calls[kernel]()
