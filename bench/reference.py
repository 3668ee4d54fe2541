"""Plain reference of a federated run: local SGD and aggregation.

It follows the paper's algorithms as written, one client at a time, in
straightforward jax.numpy, and imports nothing of the program. What it is
given is the question, not an answer: the client data the benchmark
generated, the configuration's plain model (`bench/configs/<name>.py`),
the mix's hyper-parameters, and the schedule of each update (which
satellites took part, for how many epochs, how stale).

- Local SGD: `steps = clip(epochs * max(1, n_k // batch), 1, max_steps)`
  plain SGD steps on minibatches of `batch` rows drawn uniformly from the
  client's first n_k rows, with the proximal term 0.5*mu*||w - anchor||^2.
  The keys follow the published stream: PRNGKey(seed) splits into (rng,
  init); each update splits rng into (rng, sub) and sub into one key per
  client; each step splits the client's key into (key, minibatch key).
- Synchronous update (FedAvg): w <- sum_k (n_k / m) w_k.
- Buffered update (FedBuff): w <- w + lr_g * sum_k (a_k / sum a) (w_k - w)
  with a_k = n_k * [tau_k <= max_staleness] / sqrt(1 + tau_k); client k
  starts from, and is anchored to, the version it downloaded.

`dtype` sets the precision the whole computation runs in: float32 for
the reference (under matmul precision "highest"), bfloat16 for the
control. `fault` plants one of `FAULTS`, so that the reference put in the
program's place shows what each fault reads.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


FAULTS = (
    "half_clients",       # the update averages the first half of clients
    "client_unchanged",   # one satellite's answer is its start model
)


def client_steps(n_k: int, epochs: int, batch: int, max_steps: int) -> int:
    return int(min(max(epochs * max(1, n_k // batch), 1), max_steps))


@dataclasses.dataclass(frozen=True)
class Update:
    """One global update of the schedule."""

    clients: tuple[int, ...]
    epochs: tuple[int, ...]
    staleness: tuple[int, ...]


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


class Reference:
    """The plain federated computation for one model and one mix."""

    def __init__(self, model, cfg: dict, mix: dict, data: dict,
                 dtype=jnp.float32, fault: str | None = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
        self.model, self.cfg, self.mix, self.data = model, cfg, mix, data
        self.dtype, self.fault = dtype, fault
        alg = mix["algorithm"]
        self.sync = alg["synchronous"]
        self.mu = float(alg.get("prox_mu", 0.0))
        self.lr = float(mix["lr"])
        self.batch = int(mix["batch_size"])
        self._sgd = jax.jit(functools.partial(
            _local_sgd, model.apply, self.lr, self.batch, dtype))

    def init(self, seed: int):
        rng, init_key = jax.random.split(jax.random.PRNGKey(seed))
        return rng, self.model.init(self.cfg, init_key)

    def local(self, params, anchor, k: int, epochs: int, key):
        n_k = int(self.data["n"][k])
        steps = client_steps(n_k, epochs, self.batch, self.mix["max_steps"])
        return self._sgd(_cast(params, self.dtype), _cast(anchor, self.dtype),
                         jnp.asarray(self.data["x"][k], self.dtype),
                         jnp.asarray(self.data["y"][k]), n_k, steps,
                         jnp.asarray(self.mu, self.dtype), key)

    def aggregate(self, params, returns, clients, staleness):
        n = np.asarray([float(self.data["n"][k]) for k in clients])
        dt = self.dtype
        if self.sync:
            w = n / n.sum()
            return jax.tree.map(
                lambda *xs: sum(jnp.asarray(wk, dt) * x
                                for wk, x in zip(w, xs)), *returns)
        alg = self.mix["algorithm"]
        tau = np.asarray(staleness, np.float64)
        a = n * (tau <= alg["max_staleness"]) / np.sqrt(1.0 + tau)
        w = a / a.sum() if a.sum() > 0 else a
        lr_g = jnp.asarray(alg["server_lr"], dt)
        return jax.tree.map(
            lambda g, *xs: g + lr_g * sum(jnp.asarray(wk, dt) * (x - g)
                                          for wk, x in zip(w, xs)),
            _cast(params, dt), *returns)

    def follow(self, seed: int, schedule: list[Update]):
        """Run `schedule` from the seed. Returns (init params, params after
        each update)."""
        rng, params = self.init(seed)
        init = params
        history = [params]                      # version v -> params
        after = []
        for r, upd in enumerate(schedule):
            rng, sub = jax.random.split(rng)
            keys = jax.random.split(sub, len(upd.clients))
            returns = []
            for i, (k, e) in enumerate(zip(upd.clients, upd.epochs)):
                start = history[r - upd.staleness[i]]
                returns.append(self.local(start, start, k, e, keys[i]))
            clients, stale = upd.clients, upd.staleness
            if self.fault == "half_clients":
                h = max(1, len(clients) // 2)
                returns, clients, stale = returns[:h], clients[:h], stale[:h]
            elif self.fault == "client_unchanged":
                returns[0] = _cast(history[r - stale[0]], self.dtype)
            params = self.aggregate(params, returns, clients, stale)
            history.append(params)
            after.append(params)
        return init, after


def _local_sgd(apply, lr, batch, dtype, params, anchor, x, y, n_k, steps,
               mu, key):
    def loss(p, xb, yb):
        logp = jax.nn.log_softmax(apply(p, xb), axis=-1)
        data = -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=-1))
        prox = sum(jnp.sum((a - b) ** 2) for a, b in
                   zip(jax.tree.leaves(p), jax.tree.leaves(anchor)))
        return data + 0.5 * mu * prox

    def step(_, carry):
        p, k = carry
        k, sub = jax.random.split(k)
        idx = jax.random.randint(sub, (batch,), 0, jnp.maximum(n_k, 1))
        g = jax.grad(loss)(p, x[idx], y[idx])
        return jax.tree.map(lambda a, b: a - jnp.asarray(lr, dtype) * b,
                            p, g), k

    return jax.lax.fori_loop(0, steps, step, (params, key))[0]
