"""Plain reference of `femnist_cnn`.

conv 3x3 SAME 1 -> 8, ReLU, 2x2 max pool; conv 3x3 SAME 8 -> 16, ReLU,
2x2 max pool; dense 784 -> 56, ReLU; dense 56 -> 47. Convolutions are
sums of nine shifted matmuls and pools a reshape and max, written apart
from the program's own lowering. It imports nothing of the program; its
data loss is the benchmark's `softmax_cross_entropy`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import softmax_cross_entropy


def forward_flops(cfg: dict) -> int:
    """Multiply-add FLOPs of one sample's forward pass (2 per MAC)."""
    side = cfg["image"][0]
    macs = 0
    for layer in cfg["layers"]:
        if layer["kind"] == "conv3x3":
            macs += side * side * 9 * layer["in"] * layer["out"]
            side //= layer.get("pool", 1)
        else:
            macs += layer["in"] * layer["out"]
    return 2 * macs


def init(cfg: dict, key) -> dict:
    c1, c2, d1, d2 = cfg["layers"]
    keys = jax.random.split(key, 4)
    he = jax.nn.initializers.he_normal()
    return {
        "conv1": {"w": he(keys[0], (3, 3, c1["in"], c1["out"]), jnp.float32),
                  "b": jnp.zeros((c1["out"],), jnp.float32)},
        "conv2": {"w": he(keys[1], (3, 3, c2["in"], c2["out"]), jnp.float32),
                  "b": jnp.zeros((c2["out"],), jnp.float32)},
        "fc1": {"w": he(keys[2], (d1["in"], d1["out"]), jnp.float32),
                "b": jnp.zeros((d1["out"],), jnp.float32)},
        "fc2": {"w": he(keys[3], (d2["in"], d2["out"]), jnp.float32),
                "b": jnp.zeros((d2["out"],), jnp.float32)},
    }


def _conv_relu_pool(x, p):
    """3x3 SAME convolution as the sum of nine shifted matmuls, then ReLU
    and a 2x2 max pool."""
    n, hh, ww, _ = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    h = sum(xp[:, dy:dy + hh, dx:dx + ww, :] @ p["w"][dy, dx]
            for dy in range(3) for dx in range(3))
    h = jax.nn.relu(h + p["b"])
    return h.reshape(n, hh // 2, 2, ww // 2, 2, -1).max(axis=(2, 4))


def apply(params: dict, x):
    h = _conv_relu_pool(x, params["conv1"])
    h = _conv_relu_pool(h, params["conv2"])
    h = h.reshape((h.shape[0], -1))
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def loss(params: dict, xb, yb):
    return softmax_cross_entropy(apply(params, xb), yb)
