"""Top-k routed mixture-of-experts.

`apply_moe` is dropless: the router scores all `n_experts`, and the
token-picks that land on the experts this chip holds (`MoEConfig.held`)
are sorted by expert and run through a grouped matmul
(`jax.lax.ragged_dot`) sized by the picks each expert got, so no token
is dropped however uneven the routing. What the absent experts would add
is left out, as expert parallelism leaves it to the chips that hold them;
the shared experts are added once, on every chip.

`apply_moe_ep` (the production-mesh all-to-all) keeps GShard-style
capacity buffers (`capacity_factor`): sort-based dispatch into an
(E, C, d_model) buffer, with overflowing tokens dropped.

Aux outputs: the balance loss of the router's kind, the softmax router's
z-loss, and `picks`, the token-picks each held expert got.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm.config import MoEConfig
from repro.models.lm.layers import apply_mlp, dense_init, init_mlp


def init_moe(rng, d_model: int, cfg: MoEConfig, mlp_kind: str) -> dict:
    ks = jax.random.split(rng, 8)
    e, ff = len(cfg.held_ids), cfg.d_ff_expert
    p = {
        "router": dense_init(ks[0], (d_model, cfg.n_experts),
                             scale=d_model ** -0.5),
        "w1": dense_init(ks[1], (e, d_model, ff)),
        "w2": dense_init(ks[2], (e, ff, d_model)),
    }
    if cfg.scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,))
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


def _route(p: dict, xf: jax.Array, cfg: MoEConfig, rows: int = 1):
    """Router + aux losses. xf: (T, d), `rows` sequences of T // rows.

    The router runs in float32 at full matmul precision, as the published
    models do: near-ties among the top-k are common, and one that breaks
    the other way sends a token to another expert."""
    E, K = cfg.n_experts, cfg.top_k
    T = xf.shape[0]
    logits = jnp.dot(xf.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)     # (T, E)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        # The bias picks the experts and weighs nothing (noaux_tc).
        _, expert_ids = jax.lax.top_k(scores + p["router_bias"], K)
        gate_vals = jnp.take_along_axis(scores, expert_ids, axis=-1)
        gate_vals = cfg.routed_scale * gate_vals / (
            gate_vals.sum(-1, keepdims=True) + 1e-20)
        # Sequence-wise balance loss (DeepSeek-V3, eqs. 17-20): per
        # sequence, f_i = E/(K S) * picks of i, P_i = mean normalised score.
        hits = jax.nn.one_hot(expert_ids, E).sum(1).reshape(rows, -1, E)
        share = (scores / scores.sum(-1, keepdims=True)).reshape(rows, -1, E)
        f = hits.mean(1) * (E / K)
        aux = {"load_balance": cfg.router_aux_coef * jnp.mean(
                   jnp.sum(f * share.mean(1), axis=-1)),
               "router_z": jnp.zeros((), jnp.float32)}
        return gate_vals, expert_ids, aux
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


@jax.custom_vjp
def _dispatch(xf, tok, inv, n_live):
    """xf[tok]: the held picks' token rows, in expert order. `inv` (S, K)
    gives each pick's place in that order (`n_live` or more: not held),
    so the backward pass sums each token's rows by gathers, with no
    scatter."""
    return xf[tok]


def _dispatch_fwd(xf, tok, inv, n_live):
    return xf[tok], (inv, n_live)


def _dispatch_bwd(res, g):
    inv, n_live = res
    # The grouped matmul leaves the rows past its groups unwritten:
    # select them away (where, not a product), and add a zero row for
    # the picks not held.
    live = (jnp.arange(g.shape[0]) < n_live)[:, None]
    gz = jnp.concatenate([jnp.where(live, g, 0.0), jnp.zeros_like(g[:1])])
    idx = jnp.minimum(inv, g.shape[0])
    dx = sum(gz[idx[:, k]] for k in range(inv.shape[1]))
    return dx, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, gates, tok, order, inv):
    """y[t] = sum_k gates[t, k] * ys[inv[t, k]], a pick not held (`inv`
    past the last row) adding nothing. Forward and backward are gathers,
    token by token or row by row."""
    return _combine_fwd(ys, gates, tok, order, inv)[0]


def _combine_fwd(ys, gates, tok, order, inv):
    yz = jnp.concatenate([ys, jnp.zeros_like(ys[:1])])
    idx = jnp.minimum(inv, ys.shape[0])
    y = sum(gates[:, k, None].astype(ys.dtype) * yz[idx[:, k]]
            for k in range(inv.shape[1]))
    return y, (yz, gates, tok, order, inv)


def _combine_bwd(res, g):
    yz, gates, tok, order, inv = res
    m = yz.shape[0] - 1
    idx = jnp.minimum(inv, m)
    dgates = jnp.stack([jnp.sum(g * yz[idx[:, k]], axis=-1)
                        for k in range(inv.shape[1])], axis=1)
    g_sorted = gates.reshape(-1)[order[:m]].astype(g.dtype)
    return (g[tok] * g_sorted[:, None], dgates.astype(gates.dtype), None,
            None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def held_experts(p: dict, xf: jax.Array, gate_vals, expert_ids,
                 cfg: MoEConfig, kind: str):
    """The held experts' part of one sequence's layer output, dropless.
    xf: (S, d); gate_vals, expert_ids: (S, K).

    Each of the S*K token-picks is mapped to a held-expert slot (H for an
    expert held elsewhere) and the picks are sorted by slot, so the held
    ones come first, grouped by expert. A token picks an expert at most
    once, so at most m = S * min(K, H) picks are held: the grouped matmul
    gets m rows and computes only the rows its groups cover. Returns
    (y (S, d), picks (H,) int32)."""
    S, d = xf.shape
    K = expert_ids.shape[-1]
    held = cfg.held_ids
    H, m = len(held), S * min(K, len(held))
    slot_of = np.full((cfg.n_experts,), H, np.int32)
    slot_of[list(held)] = np.arange(H)
    slots = jnp.asarray(slot_of)[expert_ids].reshape(-1)      # (S*K,)
    picks = jnp.sum(jax.nn.one_hot(slots, H, dtype=jnp.int32), axis=0)
    order = jnp.argsort(slots)
    inv = jnp.argsort(order).reshape(S, K).astype(jnp.int32)
    tok = order[:m] // K
    n_live = jnp.sum(picks)
    live = (jnp.arange(m) < n_live)[:, None]

    def grouped(a, w):
        # The rows past the groups are left unwritten: select them away,
        # so that no elementwise step or gradient reads them.
        return jnp.where(live, jax.lax.ragged_dot(a, w, picks), 0.0)

    xs = _dispatch(xf, tok, inv, n_live)
    h = grouped(xs, p["w1"])
    if kind == "swiglu":
        h = jax.nn.silu(h) * grouped(xs, p["w3"])
    elif kind == "geglu":
        h = jax.nn.gelu(h, approximate=True) * grouped(xs, p["w3"])
    else:
        h = jax.nn.gelu(h, approximate=True)
    y = _combine(grouped(h, p["w2"]), gate_vals, tok, order, inv)
    return y.astype(xf.dtype), picks


def apply_moe(p: dict, x: jax.Array, cfg: MoEConfig, mlp_kind: str
              ) -> tuple[jax.Array, dict]:
    """x: (B, S, d) -> (y, aux). Routed top-k over all experts, the held
    experts' part computed with no token dropped, one sequence at a time
    (its activations recomputed in the backward pass), plus the shared
    experts. aux: `load_balance`, `router_z` and `picks` (H,)."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    gate_vals, expert_ids, aux = _route(p, xf, cfg, rows=B)

    @jax.checkpoint
    def row(args):
        return held_experts(p, *args, cfg, mlp_kind)

    y, picks = jax.lax.map(row, (x, gate_vals.reshape(B, S, -1),
                                 expert_ids.reshape(B, S, -1)))
    y = y.reshape(B * S, d)
    aux["picks"] = jnp.sum(picks, axis=0)
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
