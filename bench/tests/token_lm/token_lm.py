"""Plain reference of the test-only next-token model `token_lm`:
embedding (V, D), one dense layer D -> H with ReLU, head H -> V.

A row holds seq_len + 1 token ids; its first seq_len ids predict its last
seq_len (the labels `yb` are unused). Straightforward jax.numpy; it
imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def forward_flops(cfg: dict) -> int:
    """Multiply-add FLOPs of one row's forward pass (2 per MAC); the
    embedding is a gather."""
    d, h, v = cfg["d_model"], cfg["d_hidden"], cfg["vocab_size"]
    return 2 * cfg["seq_len"] * (d * h + h * v)


def init(cfg: dict, key) -> dict:
    d, h, v = cfg["d_model"], cfg["d_hidden"], cfg["vocab_size"]
    k1, k2, k3 = jax.random.split(key, 3)
    he = jax.nn.initializers.he_normal()
    return {"embed": jax.random.normal(k1, (v, d), jnp.float32),
            "hidden": {"w": he(k2, (d, h), jnp.float32),
                       "b": jnp.zeros((h,), jnp.float32)},
            "head": {"w": jax.random.normal(k3, (h, v), jnp.float32)
                     / jnp.sqrt(jnp.float32(h)),
                     "b": jnp.zeros((v,), jnp.float32)}}


def apply(params: dict, tokens):
    """Logits (B, S, V) of the next id at each of `tokens`' (B, S) ids."""
    h = params["embed"][tokens]
    h = jax.nn.relu(h @ params["hidden"]["w"] + params["hidden"]["b"])
    return h @ params["head"]["w"] + params["head"]["b"]


def loss(params: dict, xb, yb):
    del yb
    logp = jax.nn.log_softmax(apply(params, xb[:, :-1]), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, xb[:, 1:, None], axis=-1))
