"""The plain reference streams its clients: the aggregate folds each
return as it lands, bit for bit the list-then-sum formula, and `follow`
keeps a bounded number of models on the device whatever the clients."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference

SHAPES = {"a": (7, 5), "b": (11,), "c": (3, 4, 2)}   # no two alike
K, N, F = 10, 40, 7


def _model():
    def init(cfg, key):
        keys = jax.random.split(key, len(SHAPES))
        return {name: jax.random.normal(k, shape, jnp.float32)
                for k, (name, shape) in zip(keys, SHAPES.items())}

    def loss(p, xb, yb):
        h = jnp.tanh(xb @ p["a"])                              # (B, 5)
        out = h.sum(-1) * jnp.mean(p["b"]) + jnp.mean(p["c"] ** 2)
        return jnp.mean((out - yb) ** 2)

    return types.SimpleNamespace(init=init, loss=loss)


def _reference(sync: bool, fault=None):
    rng = np.random.default_rng(5)
    data = {"x": rng.normal(size=(K, N, F)).astype(np.float32),
            "y": rng.integers(0, 3, size=(K, N)).astype(np.int32),
            "n": rng.integers(10, N + 1, size=K).astype(np.int32)}
    alg = ({"synchronous": True, "prox_mu": 0.0} if sync else
           {"synchronous": False, "prox_mu": 0.1, "max_staleness": 3,
            "server_lr": 0.8})
    mix = {"algorithm": alg, "lr": 0.05, "batch_size": 8, "max_steps": 4}
    return reference.Reference(_model(), {}, mix, data, fault=fault)


def _trees(seed: int, count: int):
    rng = np.random.default_rng(seed)
    return [{name: jnp.asarray(rng.normal(size=shape).astype(np.float32))
             for name, shape in SHAPES.items()} for _ in range(count)]


def _list_then_sum(ref, params, returns, clients, staleness):
    """The aggregate as a sum over the list of all returns."""
    w, dt = ref.weights(clients, staleness), ref.dtype
    if ref.sync:
        return jax.tree.map(lambda *xs: sum(
            jnp.asarray(wk, dt) * x for wk, x in zip(w, xs)), *returns)
    lr_g = jnp.asarray(ref.mix["algorithm"]["server_lr"], dt)
    return jax.tree.map(lambda g, *xs: g + lr_g * sum(
        jnp.asarray(wk, dt) * (x - g) for wk, x in zip(w, xs)),
        params, *returns)


def _same_bits(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


# Every staleness 0-4 appears, past FedBuff's cut-off of 3 too.
SCHEDULES = {"fedavg": (True, [(0,) * K] * 3),
             "fedbuff": (False, [(0,) * K, (0, 1) * 5, (2, 0, 1) * 3 + (0,),
                                 (3, 1, 0, 2, 0, 3, 1, 2, 0, 1),
                                 (4, 0, 1, 2, 3, 4, 0, 1, 2, 3)])}


@pytest.mark.parametrize("kind", sorted(SCHEDULES))
def test_streamed_aggregate_is_the_list_then_sum(kind):
    sync, stales = SCHEDULES[kind]
    ref = _reference(sync)
    clients = tuple(range(K))
    for i, stale in enumerate(stales):
        params, *returns = _trees(100 + i, K + 1)
        want = _list_then_sum(ref, params, returns, clients, stale)
        got = ref.aggregate(params, iter(returns), clients, stale)
        assert _same_bits(got, want), (kind, stale)


def _list_follow(ref, seed, schedule):
    """`follow` as the list-then-sum formula: every version and every
    return of an update held at once."""
    rng, params = ref.init(seed)
    history = [params]
    for r, upd in enumerate(schedule):
        rng, sub = jax.random.split(rng)
        keys = jax.random.split(sub, len(upd.clients))
        returns = [ref.local(history[r - s], history[r - s], k, e, keys[i])
                   for i, (k, e, s) in enumerate(zip(
                       upd.clients, upd.epochs, upd.staleness))]
        params = _list_then_sum(ref, reference._cast(params, ref.dtype),
                                returns, upd.clients, upd.staleness)
        history.append(params)
    return history[0], history[1:]


def _schedule(stales):
    order = np.random.default_rng(9)
    return [reference.Update(clients=tuple(order.permutation(K).tolist()),
                             epochs=tuple(1 + j % 3 for j in range(K)),
                             staleness=tuple(min(s, r) for s in stale))
            for r, stale in enumerate(stales)]


@pytest.mark.parametrize("kind", sorted(SCHEDULES))
def test_follow_holds_at_most_four_models_on_the_device(kind, monkeypatch):
    sync, stales = SCHEDULES[kind]
    ref = _reference(sync)
    schedule = _schedule(stales)
    live = []

    def models_on_device():
        shapes = [a.shape for a in jax.live_arrays()]
        return max(shapes.count(s) for s in SHAPES.values())

    local = ref.local

    def counted(*args):
        live.append(models_on_device())
        out = local(*args)
        live.append(models_on_device())
        return out

    monkeypatch.setattr(ref, "local", counted)
    init, after = ref.follow(2 ** 31 + 3, schedule)
    assert len(live) == 2 * K * len(schedule)
    assert max(live) <= 4, live
    monkeypatch.setattr(ref, "local", local)
    want_init, want_after = _list_follow(ref, 2 ** 31 + 3, schedule)
    assert _same_bits(init, want_init)
    assert len(after) == len(schedule)
    for got, want in zip(after, want_after):
        assert isinstance(jax.tree.leaves(got)[0], np.ndarray)
        assert _same_bits(got, want)
