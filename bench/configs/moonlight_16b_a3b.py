"""Plain reference of Moonlight-16B-A3B's one-chip share (DeepSeek-V3
block): the dense layer and the first MoE layers, the routed experts
this chip holds, a slice of the vocabulary. Straightforward jax.numpy in
float32; it imports nothing of the program.

- Embedding (V, d); per layer: RMSNorm, multi-head latent attention
  (direct query projection, q_lora_rank null; keys and values from a
  512-wide latent with its RMSNorm; a 64-dim rotary key shared by the
  heads), residual; RMSNorm, then SwiGLU (the dense layer) or the expert
  layer, residual; final RMSNorm and the head over the slice.
- Expert layer: the router scores all of the published experts in
  float32, sigmoid; the 6 picked by score plus selection bias (which
  weighs nothing) give gates normalised over the six and scaled by
  `routed_scaling_factor`. The held experts' part is computed densely:
  every token through every held expert, times its gate, zero where the
  expert was not picked. The 2 shared experts are one SwiGLU of twice the
  expert width. What the experts held elsewhere add is left out, as on
  the chip that holds these.
- Loss: mean next-token cross-entropy over the slice, plus alpha times
  the sequence-wise balance loss of each MoE layer (DeepSeek-V3, eqs.
  17-20): per sequence, sum_i f_i P_i with f_i = E/(K S) * picks of i
  and P_i the mean of i's score normalised over the E experts.

Departures from the published model, all of them the configuration's
`assumed`: rotary pairs are (i, i + 32) of the 64 rope dims, a fixed
permutation of the published interleaved pairs; the init is a seeded
draw (`init`), drawn from the key in the order the program draws it,
into the same tree.

The weights are drawn from the key as the program's initialiser draws
them: the same splits of the key, the same draws, the same tree.
Attention runs in query blocks and each layer is recomputed in the
backward pass (`jax.checkpoint`), so a gradient holds one layer's and
one block's activations at a time: the same values, in less memory.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

Q_BLOCK = 512
with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as _f:
    CFG = json.load(_f)      # this configuration, as its JSON beside it


def _dims(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], r=cfg["kv_lora_rank"],
        ff=cfg["intermediate_size"], ffe=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"], vocab=cfg["vocab_size"],
        experts=cfg["deployment"]["router_experts"],
        held=tuple(cfg["deployment"]["held_experts"]),
        k=cfg["num_experts_per_tok"],
        dense=cfg["first_k_dense_replace"],
        moe=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])


def forward_flops(cfg: dict) -> int:
    """FLOPs (2 per multiply-add) of one row of `seq_len` tokens forward:
    the projections, attention's causal half, the dense and shared MLPs,
    the routed experts at the expected top_k * held / router_experts a
    token (so uneven routing is under-read, never over-read), the router
    and the head over the slice; the embedding is a gather."""
    m, s = _dims(cfg), cfg["seq_len"]
    d, h = m["d"], m["heads"]
    proj = (d * h * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"])
            + m["r"] * h * (m["nope"] + m["v"]) + h * m["v"] * d)
    layers = m["dense"] + m["moe"]
    attn = layers * h * (m["nope"] + m["rope"] + m["v"]) * s * (s + 1) // 2
    dense = m["dense"] * 3 * d * m["ff"]
    routed = 3 * d * m["ffe"] * m["k"] * len(m["held"]) / m["experts"]
    moe = m["moe"] * (3 * d * m["ffe"] * m["shared"] + routed
                      + d * m["experts"])
    macs = s * (layers * proj + dense + moe + d * m["vocab"]) + attn
    return int(2 * macs)


# ---------------------------------------------------------------- init --
def _matrix(key, shape):
    """Truncated normal (+-2 sd), sd fan_in ** -0.5 (fan_in: shape[-2])."""
    return shape[-2] ** -0.5 * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def _swiglu_init(key, d, ff):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w1": _matrix(k1, (d, ff)), "w2": _matrix(k2, (ff, d)),
            "w3": _matrix(k3, (d, ff))}


def _layer_init(cfg: dict, key, moe: bool) -> dict:
    m = _dims(cfg)
    d, h = m["d"], m["heads"]
    ks = jax.random.split(key, 8)
    ka = jax.random.split(ks[0], 8)
    p = {"norm1": jnp.zeros((d,)), "norm2": jnp.zeros((d,)),
         "mla": {"wq": _matrix(ka[0], (d, h * (m["nope"] + m["rope"]))),
                 "wkv_a": _matrix(ka[2], (d, m["r"] + m["rope"])),
                 "kv_norm": jnp.zeros((m["r"],)),
                 "wk_b": _matrix(ka[3], (m["r"], h * m["nope"])),
                 "wv_b": _matrix(ka[4], (m["r"], h * m["v"])),
                 "wo": _matrix(ka[5], (h * m["v"], d))}}
    if not moe:
        p["mlp"] = _swiglu_init(ks[3], d, m["ff"])
        return p
    ke = jax.random.split(ks[3], 8)
    n = len(m["held"])
    p["moe"] = {"router": _matrix(ke[0], (d, m["experts"])),
                "w1": _matrix(ke[1], (n, d, m["ffe"])),
                "w2": _matrix(ke[2], (n, m["ffe"], d)),
                "router_bias": jnp.zeros((m["experts"],)),
                "w3": _matrix(ke[3], (n, d, m["ffe"])),
                "shared": _swiglu_init(ke[4], d, m["ffe"] * m["shared"])}
    return p


def init(cfg: dict, key) -> dict:
    m = _dims(cfg)
    ks = jax.random.split(key, 10)
    segments = []
    for i, (count, moe) in enumerate(((m["dense"], False),
                                      (m["moe"], True))):
        keys = jax.random.split(ks[2 + i], count)
        segments.append(jax.vmap(lambda k, moe=moe: _layer_init(
            cfg, k, moe))(keys))
    return {"embed": 0.02 * jax.random.normal(ks[0], (m["vocab"], m["d"]),
                                              jnp.float32),
            "final_norm": jnp.zeros((m["d"],)),
            "lm_head": _matrix(ks[1], (m["d"], m["vocab"])),
            "segments": segments}


# ------------------------------------------------------------- forward --
def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rotate(x, theta):
    """Rotary embedding of x (B, S, ..., D) at positions 0..S-1: pairs
    (i, i + D/2) turned by position * theta ** (-2i / D)."""
    s, dim = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (dim // 2,))
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _attention(p, x, cfg):
    m = _dims(cfg)
    b, s, _ = x.shape
    h, nope, rope = m["heads"], m["nope"], m["rope"]
    q = (x @ p["wq"]).reshape(b, s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         _rotate(q[..., nope:], cfg["rope_theta"])], -1)
    kv = x @ p["wkv_a"]
    c = _rmsnorm(kv[..., :m["r"]], p["kv_norm"], cfg["rms_norm_eps"])
    k_rope = _rotate(kv[..., m["r"]:], cfg["rope_theta"])     # (b, s, rope)
    k = jnp.concatenate(
        [(c @ p["wk_b"]).reshape(b, s, h, nope),
         jnp.broadcast_to(k_rope[:, :, None], (b, s, h, rope))], -1)
    v = (c @ p["wv_b"]).reshape(b, s, h, m["v"])
    scale = (nope + rope) ** -0.5

    @jax.checkpoint
    def block(q_blk, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * scale
        pos = start + jnp.arange(q_blk.shape[1])
        causal = pos[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    out = jnp.concatenate([block(q[:, i:i + Q_BLOCK], i)
                           for i in range(0, s, Q_BLOCK)], axis=1)
    return out.reshape(b, s, h * m["v"]) @ p["wo"]


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def _experts(p, x, cfg):
    """The held experts' and the shared experts' output, and the
    sequence-wise balance loss."""
    m = _dims(cfg)
    b, s, d = x.shape
    e, k = m["experts"], m["k"]
    logits = jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)                           # (b, s, E)
    _, picked = jax.lax.top_k(scores + p["router_bias"], k)   # (b, s, K)
    gates = jnp.take_along_axis(scores, picked, -1)
    gates = cfg["routed_scaling_factor"] * gates / (
        jnp.sum(gates, -1, keepdims=True) + 1e-20)
    y = _swiglu(p["shared"], x)
    for i, expert in enumerate(m["held"]):
        gate = jnp.sum(jnp.where(picked == expert, gates, 0.0), -1)
        w = {n: p[n][i] for n in ("w1", "w2", "w3")}
        y = y + gate[..., None] * _swiglu(w, x)
    f = jnp.sum(jax.nn.one_hot(picked, e), axis=(1, 2)) * e / (k * s)
    share = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    balance = jnp.mean(jnp.sum(f * share, -1))
    return y, cfg["assumed"]["balance_alpha"] * balance


def _layer(p, x, cfg, moe: bool):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(p["mla"], _rmsnorm(x, p["norm1"], eps), cfg)
    h = _rmsnorm(x, p["norm2"], eps)
    if moe:
        y, balance = _experts(p["moe"], h, cfg)
        return x + y, balance
    return x + _swiglu(p["mlp"], h), jnp.zeros((), jnp.float32)


def _trunk(params: dict, tokens, cfg: dict):
    """(logits (B, S, V), summed balance losses) of `tokens` (B, S)."""
    x = params["embed"][tokens]
    balance = jnp.zeros((), jnp.float32)
    for seg, moe in zip(params["segments"], (False, True)):
        layer = jax.checkpoint(lambda lp, x, moe=moe: _layer(lp, x, cfg,
                                                             moe))
        for i in range(jax.tree.leaves(seg)[0].shape[0]):
            x, bal = layer(jax.tree.map(lambda a: a[i], seg), x)
            balance = balance + bal
    x = _rmsnorm(x, params["final_norm"], cfg["rms_norm_eps"])
    return x @ params["lm_head"], balance


def data_loss(cfg: dict, params: dict, xb):
    """Mean next-token cross-entropy of rows `xb` (B, S + 1), plus the
    balance losses, under configuration `cfg`."""
    logits, balance = _trunk(params, xb[:, :-1], cfg)
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.mean(jnp.take_along_axis(logp, xb[:, 1:, None], -1))
    return ce + balance


def apply(params: dict, tokens):
    """Logits (B, S, V) of the next id at each of `tokens`' (B, S) ids."""
    return _trunk(params, tokens, CFG)[0]


def loss(params: dict, xb, yb):
    """The data loss of this configuration (`yb` is unused)."""
    del yb
    return data_loss(CFG, params, xb)
