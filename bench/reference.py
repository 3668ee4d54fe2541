"""Plain reference of a federated run: local SGD and aggregation.

It follows the paper's algorithms as written, one client at a time, in
straightforward jax.numpy, and imports nothing of the program. What it is
given is the question, not an answer: the client data the benchmark
generated, the configuration's plain model (`bench/configs/<name>.py`:
`init`, `apply` and its data loss `loss(params, xb, yb)`), the mix's
hyper-parameters, and the schedule of each update (which satellites took
part, for how many epochs, how stale).

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

It holds O(1) models on the device, whatever the number of clients and
their staleness: each client's weighted contribution is folded into one
accumulator as it lands, in client order and with the operations of the
list-then-sum formula (`aggregate`), and the global versions stay on the
host, a version going up when a client starts from it (`follow`).

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


def softmax_cross_entropy(logits, labels):
    """The classifiers' data loss: mean cross-entropy of `labels` under
    the softmax of `logits`."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@dataclasses.dataclass(frozen=True)
class Update:
    """One global update of the schedule."""

    clients: tuple[int, ...]
    epochs: tuple[int, ...]
    staleness: tuple[int, ...]


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def _to_host(tree):
    """A copy in host memory: `np.asarray` alone may alias the device
    buffer (as on the CPU backend) and keep it alive."""
    return jax.tree.map(lambda a: np.array(a, copy=True), tree)


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
            _local_sgd, model.loss, self.lr, self.batch, dtype))

    def init(self, seed: int):
        rng, init_key = jax.random.split(jax.random.PRNGKey(seed))
        return rng, self.model.init(self.cfg, init_key)

    def local(self, params, anchor, k: int, epochs: int, key):
        n_k = int(self.data["n"][k])
        steps = client_steps(n_k, epochs, self.batch, self.mix["max_steps"])
        x = self.data["x"][k]
        # Features take the run's precision; token ids stay integers.
        x_dtype = self.dtype if np.issubdtype(x.dtype, np.floating) else None
        return self._sgd(_cast(params, self.dtype), _cast(anchor, self.dtype),
                         jnp.asarray(x, x_dtype),
                         jnp.asarray(self.data["y"][k]), n_k, steps,
                         jnp.asarray(self.mu, self.dtype), key)

    def weights(self, clients, staleness) -> np.ndarray:
        """Each client's aggregation weight, from the schedule alone."""
        n = np.asarray([float(self.data["n"][k]) for k in clients])
        if self.sync:
            return n / n.sum()
        alg = self.mix["algorithm"]
        tau = np.asarray(staleness, np.float64)
        a = n * (tau <= alg["max_staleness"]) / np.sqrt(1.0 + tau)
        return a / a.sum() if a.sum() > 0 else a

    def aggregate(self, params, returns, clients, staleness):
        """The update from `params` given the clients' `returns`, an
        iterable in client order. Each return is folded into one
        accumulator as it lands and then dropped: the same additions, in
        the same order, as `sum` over the list of weighted returns."""
        dt = self.dtype
        g = None if self.sync else _cast(params, dt)
        acc = jax.tree.map(lambda _: 0, params)
        weights = iter(self.weights(clients, staleness))
        for x in returns:       # no zip: its tuple would keep x alive
            wk = jnp.asarray(next(weights), dt)
            if self.sync:
                acc = jax.tree.map(lambda a, xi: a + wk * xi, acc, x)
            else:
                acc = jax.tree.map(lambda a, gi, xi: a + wk * (xi - gi),
                                   acc, g, x)
            del x
        if self.sync:
            return acc
        lr_g = jnp.asarray(self.mix["algorithm"]["server_lr"], dt)
        return jax.tree.map(lambda gi, a: gi + lr_g * a, g, acc)

    def follow(self, seed: int, schedule: list[Update]):
        """Run `schedule` from the seed. Returns (init params, params after
        each update), on the host."""
        rng, params = self.init(seed)
        versions = [_to_host(params)]           # version v -> params, host
        for r, upd in enumerate(schedule):
            rng, sub = jax.random.split(rng)
            keys = jax.random.split(sub, len(upd.clients))
            clients, stale = upd.clients, upd.staleness
            if self.fault == "half_clients":
                h = max(1, len(clients) // 2)
                clients, stale = clients[:h], stale[:h]
            returns = self._returns(params, versions, r, clients, upd.epochs,
                                    stale, keys)
            params = self.aggregate(params, returns, clients, stale)
            versions.append(_to_host(params))
        return versions[0], versions[1:]

    def _returns(self, params, versions, r, clients, epochs, stale, keys):
        """Each client's returned model in turn, trained from the version
        it downloaded: the current `params`, already on the device, or an
        older one uploaded from the host for this client alone."""
        for i, (k, e, s) in enumerate(zip(clients, epochs, stale)):
            start = params if s == 0 else jax.tree.map(jnp.asarray,
                                                       versions[r - s])
            if self.fault == "client_unchanged" and i == 0:
                yield _cast(start, self.dtype)
            else:
                yield self.local(start, start, k, e, keys[i])


def _local_sgd(data_loss, lr, batch, dtype, params, anchor, x, y, n_k, steps,
               mu, key):
    def loss(p, xb, yb):
        data = data_loss(p, xb, yb)
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
