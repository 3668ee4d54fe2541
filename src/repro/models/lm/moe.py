"""Top-k routed mixture-of-experts with sort-based capacity dispatch.

The dispatch is GShard-style but without the (T, E, C) one-hot tensor:
token->expert assignments are sorted, positions within each expert group
are computed from cumulative counts, and tokens scatter into an
(E, C, d_model) buffer that feeds *batched* per-expert matmuls
(einsum over the expert axis — MXU-friendly, shards cleanly: E over the
fsdp axes, expert d_ff over the model axis). Overflowing tokens are
dropped (capacity_factor controls slack), underfull slots are zero.

Aux outputs: switch-style load-balance loss + router z-loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.lm.config import MoEConfig
from repro.models.lm.layers import apply_mlp, dense_init, init_mlp


def init_moe(rng, d_model: int, cfg: MoEConfig, mlp_kind: str) -> dict:
    ks = jax.random.split(rng, 8)
    e, ff = cfg.n_experts, cfg.d_ff_expert
    p = {
        "router": dense_init(ks[0], (d_model, e), scale=d_model ** -0.5),
        "w1": dense_init(ks[1], (e, d_model, ff)),
        "w2": dense_init(ks[2], (e, ff, d_model)),
    }
    if mlp_kind in ("swiglu", "geglu"):
        p["w3"] = dense_init(ks[3], (e, d_model, ff))
    if cfg.n_shared:
        p["shared"] = init_mlp(ks[4], d_model, ff * cfg.n_shared,
                               gated=mlp_kind in ("swiglu", "geglu"))
    return p


def _expert_ffn(p: dict, x: jax.Array, kind: str) -> jax.Array:
    """x: (E, C, d) -> (E, C, d), batched over experts."""
    h = jnp.einsum("ecd,edf->ecf", x, p["w1"])
    if kind == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", x, p["w3"])
    elif kind == "geglu":
        h = jax.nn.gelu(h, approximate=True) * jnp.einsum(
            "ecd,edf->ecf", x, p["w3"])
    else:
        h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["w2"])


def _route(p: dict, xf: jax.Array, cfg: MoEConfig):
    """Router + aux losses. xf: (T, d)."""
    E, K = cfg.n_experts, cfg.top_k
    T = xf.shape[0]
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)           # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)                                        # (E,)
    ce = jnp.zeros(E).at[expert_ids.reshape(-1)].add(1.0) / (T * K)
    aux = {
        "load_balance": E * jnp.sum(me * ce) * cfg.router_aux_coef,
        "router_z": 1e-4 * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }
    return gate_vals, expert_ids, aux


def _dispatch_tokens(xf, gate_vals, expert_ids, E: int, C: int):
    """Sort-based capacity dispatch. xf: (T, d) -> buffer (E, C, d) plus
    the combine metadata (slot, token, gate*keep)."""
    T, d = xf.shape
    K = expert_ids.shape[-1]
    flat_e = expert_ids.reshape(-1)                           # (T*K,)
    flat_t = jnp.arange(T * K) // K
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    offsets = jnp.cumsum(counts) - counts                     # (E,)
    pos_in_e = jnp.arange(T * K) - offsets[se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)          # drop slot
    buf = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].set(xf[st])
    return buf[:-1].reshape(E, C, d), (slot, st, sg, keep)


def _combine_tokens(y_slots, meta, T: int, dtype):
    slot, st, sg, keep = meta
    EC = y_slots.shape[0]
    contrib = y_slots[jnp.minimum(slot, EC - 1)] \
        * (sg * keep)[:, None].astype(dtype)
    return jnp.zeros((T, y_slots.shape[-1]), dtype).at[st].add(contrib)


# Rows shorter than this use one global dispatch (decode: S == 1).
_ROW_DISPATCH_MIN_S = 64


def apply_moe(p: dict, x: jax.Array, cfg: MoEConfig, mlp_kind: str
              ) -> tuple[jax.Array, dict]:
    """x: (B, S, d) -> (y, aux). Routed top-k + optional shared experts.

    Dispatch is *batch-row-local* for full sequences: each row sorts and
    capacity-buffers its own S*K assignments under vmap, so the token axis
    keeps its data-parallel sharding end to end — a global argsort over
    B*S tokens would force GSPMD to all-gather the whole token buffer
    (measured: the difference between a collective-bound 2000s step and a
    compute-bound one on deepseek-v3 / 256 chips; EXPERIMENTS.md §Perf).
    Capacity is enforced per row (C = ceil(S*K*cf/E)), which is also the
    per-device semantics real EP systems implement. Decode (S == 1) keeps
    the single global dispatch.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xf = x.reshape(B * S, d)
    gate_vals, expert_ids, aux = _route(p, xf, cfg)

    if S >= _ROW_DISPATCH_MIN_S:
        C = int(max(1, round(S * K * cfg.capacity_factor / E)))

        def per_row(xr, gr, er):
            buf, meta = _dispatch_tokens(xr, gr, er, E, C)
            h = _expert_ffn(p, buf, mlp_kind)
            return _combine_tokens(h.reshape(E * C, d), meta, S, x.dtype)

        y = jax.vmap(per_row)(x, gate_vals.reshape(B, S, K),
                              expert_ids.reshape(B, S, K))
        y = y.reshape(B * S, d)
    else:
        T = B * S
        C = int(max(1, round(T * K * cfg.capacity_factor / E)))
        buf, meta = _dispatch_tokens(xf, gate_vals, expert_ids, E, C)
        h = _expert_ffn(p, buf, mlp_kind)
        y = _combine_tokens(h.reshape(E * C, d), meta, T, x.dtype)

    if cfg.n_shared:
        y = y + apply_mlp(p["shared"], xf, mlp_kind)
    return y.reshape(B, S, d), aux


# ======================================================================= #
# Expert-parallel dispatch (token all-to-all) — beyond-paper optimization
# ======================================================================= #
def apply_moe_ep(p: dict, x: jax.Array, cfg: MoEConfig, mlp_kind: str,
                 dp_axes: tuple, axis: str, n_shards: int, mesh=None
                 ) -> tuple[jax.Array, dict]:
    """GShard-style expert parallelism over `axis` (manual shard_map):

    experts live sharded E/D per data shard; each shard routes its local
    tokens, buffers them per (destination shard, local expert, slot), and a
    single `all_to_all` moves tokens to their experts (and back). Traffic
    per layer ~ T_local x d (~1 GB for deepseek train_4k) instead of
    all-gathering E x d x ff expert weights (~22.5 GB) — EXPERIMENTS.md
    §Perf hillclimb A2. The "model" axis stays automatic (expert d_ff is
    still tensor-parallel inside each expert); on the multi-pod mesh the
    batch stays sharded over "pod" too, with experts replicated per pod.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    E_loc = E // n_shards

    def shard_fn(x_loc, router, w1, w2, w3, shared):
        b_loc = x_loc.shape[0]
        T_loc = b_loc * S
        xf = x_loc.reshape(T_loc, d)
        pp = {"router": router, "w1": w1, "w2": w2}
        if w3 is not None:
            pp["w3"] = w3
        gate_vals, expert_ids, aux = _route(pp, xf, cfg)
        aux = {k: jax.lax.pmean(v, dp_axes) for k, v in aux.items()}

        # per-(shard,expert) capacity for this source shard's tokens
        C = int(max(1, round(T_loc * K * cfg.capacity_factor / E)))
        buf, meta = _dispatch_tokens(xf, gate_vals, expert_ids, E, C)
        # (E, C, d) = (D, E_loc, C, d): dst-shard-major by construction.
        send = buf.reshape(n_shards, E_loc, C, d)
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (D, E_loc, C, d) — source-shard-major rows of MY experts.
        h_in = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_shards * C, d)
        h = _expert_ffn(pp, h_in, mlp_kind)
        back = h.reshape(E_loc, n_shards, C, d).transpose(1, 0, 2, 3)
        got = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        y_slots = got.reshape(E * C, d)
        y = _combine_tokens(y_slots, meta, T_loc, x_loc.dtype)
        if cfg.n_shared:
            y = y + apply_mlp(shared, xf, mlp_kind)
        return y.reshape(b_loc, S, d), aux

    from jax.sharding import PartitionSpec as P

    gated = mlp_kind in ("swiglu", "geglu")
    in_specs = (P(dp_axes), P(), P(axis), P(axis),
                P(axis) if gated else P(), P())
    out_specs = (P(dp_axes), {"load_balance": P(), "router_z": P()})
    return jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(dp_axes) | {axis},
    )(x, p["router"], p["w1"], p["w2"],
      p.get("w3"), p.get("shared"))
