"""Compile the main path's kernels and client step for a described TPU v5e.

Nothing runs: XLA's TPU compiler, installed with JAX, compiles for device 0
of a `v5e:2x2` topology that is described, not attached. That catches what
interpret mode cannot (Mosaic lowering gaps, VMEM and HBM overruns) at no
chip time. The topology is described inside a module fixture, so importing
this file touches no TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.client import vmapped_client_update
from repro.core.workload import get_workload
from repro.kernels.fedagg import fedagg
from repro.kernels.flash_attention import flash_attention
from repro.kernels.prox_sgd import prox_sgd
from repro.kernels.wkv6 import wkv6

HBM_BYTES = 16 * 10**9          # one v5e chip
N_CLIENTS = 100                 # WalkerStar(10, 10): the paper's largest grid
N_SAMPLES = 350                 # synth_femnist's largest client shard


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _femnist_params():
    wl = get_workload("femnist_mlp")
    return wl, jax.eval_shape(wl.init_fn, jax.random.PRNGKey(0))


def _n_params() -> int:
    _, params = _femnist_params()
    return sum(l.size for l in jax.tree.leaves(params))


def _fedagg(spec):
    return fedagg.lower(spec((N_CLIENTS, _n_params())), spec((N_CLIENTS,)))


def _prox_sgd(spec):
    p = spec((_n_params(),))
    return prox_sgd.lower(p, p, p, 0.05, 0.1)


def _flash_attention(spec):
    # GQA at a long-context training shape: 8 query heads over 2 KV heads.
    q = spec((1, 8, 2048, 256), jnp.bfloat16)
    kv = spec((1, 2, 2048, 256), jnp.bfloat16)
    return flash_attention.lower(q, kv, kv)


def _wkv6(spec):
    x = spec((1, 32, 2048, 64))
    return wkv6.lower(x, x, x, x, spec((1, 32, 64, 64)))


def _client_update(spec):
    """The femnist_mlp ClientUpdate vmapped over one round of 100 clients."""
    wl, params = _femnist_params()
    stacked = jax.tree.map(
        lambda l: spec((N_CLIENTS,) + l.shape, l.dtype), params)
    anchor = jax.tree.map(lambda l: spec(l.shape, l.dtype), params)
    vcu = vmapped_client_update(wl.loss_fn, lr=0.05, batch_size=32,
                                max_steps=128)
    return jax.jit(vcu).lower(
        stacked, anchor,
        spec((N_CLIENTS, N_SAMPLES) + wl.sample_shape),
        spec((N_CLIENTS, N_SAMPLES), jnp.int32),
        spec((N_CLIENTS,), jnp.int32), spec((N_CLIENTS,), jnp.int32),
        0.0, spec((N_CLIENTS, 2), jnp.uint32))


@pytest.mark.parametrize("build,kernel", [
    (_fedagg, True), (_prox_sgd, True), (_flash_attention, True),
    (_wkv6, True), (_client_update, False),
], ids=["fedagg", "prox_sgd", "flash_attention", "wkv6", "client_update"])
def test_compiles_for_v5e(one_chip, build, kernel):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = build(spec).compile()
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
