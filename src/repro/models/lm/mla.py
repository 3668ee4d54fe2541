"""Multi-head Latent Attention (DeepSeek-V2/V3).

Queries and keys/values are projected through low-rank bottlenecks; only
the compressed KV latent c_kv (kv_lora_rank) and the shared RoPE key
(rope_head_dim) are cached. Decode uses the *absorbed* form: W_uk is folded
into the query and W_uv into the output so attention runs directly in the
compressed space — the deployment trick that makes MLA's cache ~9x smaller
than GQA at DeepSeek-V3 scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.lm.attention import attention_prefill
from repro.models.lm.config import MLAConfig
from repro.models.lm.layers import apply_rope, dense_init, rmsnorm


def init_mla(rng, d_model: int, n_heads: int, cfg: MLAConfig) -> dict:
    ks = jax.random.split(rng, 8)
    qh = cfg.nope_head_dim + cfg.rope_head_dim
    if cfg.q_lora_rank is None:
        q = {"wq": dense_init(ks[0], (d_model, n_heads * qh))}
    else:
        q = {"wq_a": dense_init(ks[0], (d_model, cfg.q_lora_rank)),
             "q_norm": jnp.zeros((cfg.q_lora_rank,)),
             "wq_b": dense_init(ks[1], (cfg.q_lora_rank, n_heads * qh))}
    return {
        **q,
        "wkv_a": dense_init(
            ks[2], (d_model, cfg.kv_lora_rank + cfg.rope_head_dim)),
        "kv_norm": jnp.zeros((cfg.kv_lora_rank,)),
        "wk_b": dense_init(
            ks[3], (cfg.kv_lora_rank, n_heads * cfg.nope_head_dim)),
        "wv_b": dense_init(
            ks[4], (cfg.kv_lora_rank, n_heads * cfg.v_head_dim)),
        "wo": dense_init(ks[5], (n_heads * cfg.v_head_dim, d_model)),
    }


def _project_q(p, x, n_heads, cfg: MLAConfig, positions, theta):
    B, S, _ = x.shape
    if "wq" in p:                           # q_lora_rank None: direct
        q = x @ p["wq"]
    else:
        q = rmsnorm(x @ p["wq_a"], p["q_norm"]) @ p["wq_b"]
    q = q.reshape(
        B, S, n_heads, cfg.nope_head_dim + cfg.rope_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, theta)
    return q_nope, q_rope


def _project_kv_latent(p, x, cfg: MLAConfig, positions, theta):
    kv_a = x @ p["wkv_a"]
    c_kv, k_rope = jnp.split(kv_a, [cfg.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, theta)[:, :, 0]
    return c_kv, k_rope                     # (B,S,r), (B,S,rope_dim)


def mla_prefill(p, x, n_heads, cfg: MLAConfig, positions, theta,
                q_chunk: int = 512):
    """Full-sequence MLA. Returns (attn_out (B,S,d), cache (c_kv, k_rope))."""
    B, S, _ = x.shape
    q_nope, q_rope = _project_q(p, x, n_heads, cfg, positions, theta)
    c_kv, k_rope = _project_kv_latent(p, x, cfg, positions, theta)
    # Expand keys/values for the parallel (training/prefill) form.
    k_nope = (c_kv @ p["wk_b"]).reshape(B, S, n_heads, cfg.nope_head_dim)
    v = (c_kv @ p["wv_b"]).reshape(B, S, n_heads, cfg.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, n_heads, cfg.rope_head_dim))], -1)
    pos = positions if positions.ndim == 1 else positions[0]
    o = attention_prefill(q, k, v, pos, pos, q_chunk=q_chunk)
    out = o.reshape(B, S, n_heads * cfg.v_head_dim) @ p["wo"]
    return out, (c_kv, k_rope)


def mla_decode(p, x, cache, pos, n_heads, cfg: MLAConfig, theta):
    """Absorbed single-token decode.

    x: (B,1,d); cache: (c_kv (B,Smax,r), k_rope (B,Smax,rd)); pos scalar.
    Returns (out (B,1,d), new_cache).
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), pos)
    q_nope, q_rope = _project_q(p, x, n_heads, cfg, positions, theta)
    c_new, kr_new = _project_kv_latent(p, x, cfg, positions, theta)
    c_kv, k_rope = cache
    c_kv = jax.lax.dynamic_update_slice_in_dim(c_kv, c_new, pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(k_rope, kr_new, pos, axis=1)

    r = cfg.kv_lora_rank
    # Absorb W_uk: q_c (B,1,H,r) = q_nope @ W_uk^T per head.
    wk = p["wk_b"].reshape(r, n_heads, cfg.nope_head_dim)
    q_c = jnp.einsum("bqhd,rhd->bqhr", q_nope, wk)
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    s = (jnp.einsum("bqhr,bkr->bhqk", q_c, c_kv)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)) * scale
    k_pos = jnp.arange(c_kv.shape[1])
    s = jnp.where((k_pos <= pos)[None, None, None, :],
                  s.astype(jnp.float32), -1e30)
    prob = jax.nn.softmax(s, -1).astype(x.dtype)
    o_c = jnp.einsum("bhqk,bkr->bqhr", prob, c_kv)            # compressed
    wv = p["wv_b"].reshape(r, n_heads, cfg.v_head_dim)
    o = jnp.einsum("bqhr,rhd->bqhd", o_c, wv)                 # absorb W_uv
    out = o.reshape(B, 1, n_heads * cfg.v_head_dim) @ p["wo"]
    return out, (c_kv, k_rope)
