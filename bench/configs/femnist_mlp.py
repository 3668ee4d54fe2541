"""Plain reference of `femnist_mlp`: dense 784 -> 56 (ReLU) -> 47.

Straightforward jax.numpy, no kernels and no batching over clients. It
imports nothing of the program; its data loss is the benchmark's
`softmax_cross_entropy`. Weights are He-normal, biases zero, drawn
from the key in the order the published init draws them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import softmax_cross_entropy


def forward_flops(cfg: dict) -> int:
    """Multiply-add FLOPs of one sample's forward pass (2 per MAC)."""
    return sum(2 * layer["in"] * layer["out"] for layer in cfg["layers"])


def init(cfg: dict, key) -> dict:
    (l1, l2) = cfg["layers"]
    k1, k2 = jax.random.split(key)
    he = jax.nn.initializers.he_normal()
    return {"fc1": {"w": he(k1, (l1["in"], l1["out"]), jnp.float32),
                    "b": jnp.zeros((l1["out"],), jnp.float32)},
            "fc2": {"w": he(k2, (l2["in"], l2["out"]), jnp.float32),
                    "b": jnp.zeros((l2["out"],), jnp.float32)}}


def apply(params: dict, x):
    h = x.reshape((x.shape[0], -1))
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def loss(params: dict, xb, yb):
    return softmax_cross_entropy(apply(params, xb), yb)
