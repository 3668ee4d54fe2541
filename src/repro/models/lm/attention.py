"""GQA attention: q-chunked prefill/train, single-token decode, ring cache.

The prefill path streams query chunks against the full K/V with an
explicit mask — memory is O(S * chunk) per head instead of O(S^2), so
prefill_32k lowers without a quadratic temporary. (On real TPU the Pallas
flash kernel in `repro.kernels.flash_attention` replaces the inner block;
the dry-run keeps the XLA-only path because Mosaic kernels cannot be
compiled by the CPU backend.)

Sliding-window decode uses a ring cache of `window` slots: slot i holds the
most recent position p with p % window == i.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def attention_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array, k_pos: jax.Array,
                      window: int | None = None,
                      softcap: float | None = None,
                      causal: bool = True,
                      q_chunk: int = 512) -> jax.Array:
    """q: (B,S,H,D), k/v: (B,Sk,KV,D), q_pos: (S,), k_pos: (Sk,).

    Returns (B,S,H,D). H must be a multiple of KV (GQA).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    Dv = v.shape[-1]                 # may differ from D (MLA nope+rope keys)
    rep = H // KV
    scale = D ** -0.5
    chunk = min(q_chunk, S)
    pad = (-S) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
    n = (S + pad) // chunk
    qg = q.reshape(B, n, chunk, KV, rep, D).transpose(1, 0, 2, 3, 4, 5)
    qp = q_pos.reshape(n, chunk)

    def body(_, xs):
        qi, qpi = xs                                  # (B,c,KV,rep,D), (c,)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qi, k) * scale
        s = _softcap(s, softcap)
        m = jnp.ones((chunk, k.shape[1]), bool)
        if causal:
            m &= qpi[:, None] >= k_pos[None, :]
        if window is not None:
            m &= (qpi[:, None] - k_pos[None, :]) < window
        s = jnp.where(m[None, None, None], s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
        return None, o

    # Each chunk's scores are recomputed in the backward pass, so a
    # gradient also holds one chunk's (chunk x Sk) scores at a time.
    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qg, qp))
    o = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S + pad, H, Dv)
    return o[:, :S]


def ring_slot(pos: jax.Array, window: int | None, max_seq: int) -> jax.Array:
    """Cache slot for a token at `pos`."""
    return pos % window if window is not None else pos % max_seq


def cache_positions(pos: jax.Array, n_slots: int, window: int | None
                    ) -> jax.Array:
    """Reconstruct the token position held in each cache slot after writing
    position `pos` (scalar). Slots not yet written get -1 (masked)."""
    idx = jnp.arange(n_slots)
    if window is None:
        kp = idx
        return jnp.where(idx <= pos, kp, -1)
    # slot i holds the latest p <= pos with p % window == i
    delta = (pos - idx) % window
    kp = pos - delta
    return jnp.where(kp >= 0, kp, -1)


def attention_decode(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, window: int | None = None,
                     softcap: float | None = None) -> jax.Array:
    """One-token attention against a (possibly ring) cache.

    q: (B,1,H,D); cache_k/v: (B,Smax,KV,D); pos: scalar current position.
    """
    B, _, H, D = q.shape
    KV = cache_k.shape[2]
    rep = H // KV
    k_pos = cache_positions(pos, cache_k.shape[1], window)    # (Smax,)
    s = jnp.einsum("bqgrd,bkgd->bgrqk",
                   q.reshape(B, 1, KV, rep, D), cache_k) * (D ** -0.5)
    s = _softcap(s, softcap)
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window is not None:
        valid &= (pos - k_pos) < window
    s = jnp.where(valid[None, None, None, None, :],
                  s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(cache_v.dtype)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, cache_v)
    return o.reshape(B, 1, H, D)


def cache_update(cache: jax.Array, new: jax.Array, pos: jax.Array,
                 window: int | None) -> jax.Array:
    """Write one token's K or V (B,1,KV,D) into the cache at its ring slot."""
    slot = ring_slot(pos, window, cache.shape[1])
    return jax.lax.dynamic_update_slice_in_dim(cache, new, slot, axis=1)
