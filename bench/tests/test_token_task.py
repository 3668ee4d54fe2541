"""A token-trained configuration through the whole harness, on the CPU.

`token_lm/` holds a test-only configuration (kept out of
`BENCHMARK.json`): its JSON names the token generator, its plain file the
next-token data loss, beside them its limits and two mixes at c2s5. The
program side is a small next-token model (embedding, one dense layer, a
vocabulary head) registered here as a workload of the program's own. A
sound run comes out correct; each fault of `small.FAULTS` planted in the
program's timed path reads above a limit, and so does the bfloat16
control, which keeps the token ids integers.

The limits, 1e-4 on `first_gap` and `change_gap`, sit between readings on
the CPU: sound runs read at most 2.9e-7 over 12 seeds a mix, and every
faulted run reads 0.016 or more on one of the two (4 seeds a mix and
fault).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, check, harness, reference
from bench.cell import client_data
from bench.tests import small

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "token_lm")
MIXES = ("c2s5-fedavg_sched", "c2s5-fedbuff")
EXACT = {"runs_differ": 0.0, "plan_differs": 0.0}


def _workload(cfg, plain):
    """The program's side: its client loop, aggregation and evaluation
    run this model; the initial draw is the configuration's."""
    from repro.core.client import cross_entropy
    from repro.core.workload import Workload
    from repro.data.federated import FederatedDataset

    def logits(p, ids):
        h = jnp.take(p["embed"], ids, axis=0)
        h = jax.nn.relu(h @ p["hidden"]["w"] + p["hidden"]["b"])
        return h @ p["head"]["w"] + p["head"]["b"]

    def loss_fn(p, xb, yb):
        del yb
        out = logits(p, xb[:, :-1])
        return jnp.mean(cross_entropy(out.reshape(-1, out.shape[-1]),
                                      xb[:, 1:].reshape(-1)))

    @jax.jit
    def eval_fn(p, x, y, n_valid):
        del y
        hit = (jnp.argmax(logits(p, x[:, :, :-1]), -1) == x[:, :, 1:])
        rows = jnp.mean(hit.astype(jnp.float32), axis=-1)      # (K, N)
        mask = (jnp.arange(x.shape[1])[None, :]
                < n_valid[:, None]).astype(jnp.float32)
        return jnp.sum(rows * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def make_data(n_clients, seed=0):
        mix = harness._json(HERE, MIXES[0] + ".json")
        return FederatedDataset(**client_data(cfg, mix, n_clients, seed))

    return Workload(
        name=cfg["workload"], init_fn=functools.partial(plain.init, cfg),
        loss_fn=loss_fn, eval_fn=eval_fn, make_data=make_data,
        sample_shape=(cfg["seq_len"] + 1,), sample_dtype="int32",
        flops_per_sample=3.0 * plain.forward_flops(cfg), samples_per_epoch=24)


@pytest.fixture(scope="module")
def token_spec():
    """The spec of a token cell under mix `name`, with the program's
    workload registered for as long as the module's tests run."""
    from repro.core import workload

    cfg = harness._json(HERE, "token_lm.json")
    plain = harness._module(os.path.join(HERE, "token_lm.py"),
                            "bench_test_token_lm")
    workload.register_workload(cfg["workload"],
                               lambda: _workload(cfg, plain))

    def spec(name):
        base = harness.load_spec("mlp-fedavg_sched-c10s10-g13")
        return dict(base, cfg=cfg, model=plain,
                    mix=harness._json(HERE, name + ".json"),
                    limits=harness._json(HERE, "limits.json"))
    yield spec
    workload._BUILDERS.pop(cfg["workload"], None)
    workload._CACHE.pop(cfg["workload"], None)


@pytest.mark.parametrize("mix", MIXES)
def test_sound_token_run_is_correct(token_spec, mix):
    out = small.run(token_spec(mix))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


@pytest.mark.parametrize("mix,fault", [(m, f) for m in MIXES
                                       for f in sorted(small.FAULTS)])
def test_token_fault_is_not_correct(token_spec, mix, fault, monkeypatch):
    small.FAULTS[fault](monkeypatch)
    out = small.run(token_spec(mix))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("mix", MIXES)
def test_bfloat16_control_keeps_ids_and_fails(token_spec, mix, monkeypatch):
    """The control of `calibrate.readings` trains in bfloat16 on int32
    ids, and reads above the token limits where the program does not."""
    seen = []

    def spy(data_loss, lr, batch, dtype, params, anchor, x, *rest):
        seen.append((jnp.dtype(dtype), x.dtype))
        return orig(data_loss, lr, batch, dtype, params, anchor, x, *rest)
    orig = reference._local_sgd
    monkeypatch.setattr(reference, "_local_sgd", spy)
    spec = token_spec(mix)
    row = calibrate.readings(spec, 2 ** 31 + 11, control=True, faults=False)
    assert (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.int32)) in seen
    assert all(x == jnp.int32 for _, x in seen), seen
    ok, rows = check.verdict(dict(row["program"], **EXACT), spec["limits"])
    assert ok, rows
    ok, rows = check.verdict(dict(row["control"], **EXACT), spec["limits"])
    assert not ok, rows
