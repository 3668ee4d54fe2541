"""Composable transformer stack covering all assigned architectures.

A model is a list of scanned *segments* (config.Segment). Per-layer kinds:
  attn    — [MLA|GQA] attention + dense MLP
  moe     — [MLA|GQA] attention + routed experts (+ shared)
  rwkv    — RWKV6 time mix + channel mix
  hybrid  — parallel GQA attention + SSD heads, then dense MLP

Three entry points, one per input-shape class:
  forward_train(cfg, params, tokens, ...)          -> (logits, aux)
  prefill(cfg, params, tokens, max_seq, ...)       -> (logits, cache)
  decode_step(cfg, params, token, cache)           -> (logits, cache)

Enc-dec (Whisper): `encoder_forward` runs the bidirectional stack over the
stubbed frame embeddings; decoder layers grow a cross-attention block and
cache the encoder K/V at prefill.

All heavy paths are pure jnp/lax — they lower on any backend; Pallas
kernels swap in at the ops layer on real TPUs.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.lm.attention import (
    attention_decode,
    attention_prefill,
    cache_update,
)
from repro.models.lm.config import ModelConfig, Segment
from repro.models.lm.layers import (
    apply_mlp,
    apply_rope,
    dense_init,
    init_mlp,
    rmsnorm,
)
from repro.models.lm.mla import init_mla, mla_decode, mla_prefill
from repro.models.lm.moe import apply_moe, apply_moe_ep, init_moe
from repro.models.lm.rwkv import (
    init_rwkv_channel_mix,
    init_rwkv_time_mix,
    rwkv_channel_mix,
    rwkv_time_mix,
    rwkv_time_mix_step,
)
from repro.models.lm.ssm import CONV_K, init_ssm, ssm_forward, ssm_step
from repro.sharding.ctx import constrain_batch, constrain_kv, ep_axis

Pytree = Any


# ======================================================================= #
# Init
# ======================================================================= #
def _init_gqa(rng, cfg: ModelConfig) -> dict:
    hd = cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads * hd)),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd)),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd)),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, cfg.d_model)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,))
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,))
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,))
    return p


def _init_layer(cfg: ModelConfig, seg: Segment, rng,
                cross_attention: bool = False) -> dict:
    ks = jax.random.split(rng, 8)
    p: dict = {"norm1": jnp.zeros((cfg.d_model,)),
               "norm2": jnp.zeros((cfg.d_model,))}
    if seg.kind == "rwkv":
        p["tm"] = init_rwkv_time_mix(ks[0], cfg.d_model,
                                     cfg.resolved_head_dim)
        p["cm"] = init_rwkv_channel_mix(ks[1], cfg.d_model, cfg.d_ff)
        return p
    # attention piece
    if cfg.mla is not None and seg.kind in ("attn", "moe"):
        p["mla"] = init_mla(ks[0], cfg.d_model, cfg.n_heads, cfg.mla)
    else:
        p["attn"] = _init_gqa(ks[0], cfg)
    if cross_attention:
        p["xattn"] = _init_gqa(ks[1], cfg)
        p["norm_x"] = jnp.zeros((cfg.d_model,))
    if seg.kind == "hybrid":
        p["ssm"] = init_ssm(ks[2], cfg.d_model, cfg.ssm)
        p["gate_attn"] = jnp.zeros(())
        p["gate_ssm"] = jnp.zeros(())
    # ffn piece
    if seg.kind == "moe":
        p["moe"] = init_moe(ks[3], cfg.d_model, cfg.moe, cfg.mlp)
    else:
        p["mlp"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff,
                            gated=cfg.mlp in ("swiglu", "geglu"))
    return p


def init_params(cfg: ModelConfig, rng) -> dict:
    ks = jax.random.split(rng, 8 + len(cfg.resolved_segments))
    dt = jnp.dtype(cfg.dtype)
    params: dict = {
        "embed": 0.02 * jax.random.normal(
            ks[0], (cfg.vocab_size, cfg.d_model), jnp.float32),
        "final_norm": jnp.zeros((cfg.d_model,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size))
    segs = []
    for i, seg in enumerate(cfg.resolved_segments):
        lks = jax.random.split(ks[2 + i], seg.n_layers)
        segs.append(jax.vmap(
            lambda k: _init_layer(cfg, seg, k, cross_attention=False))(lks))
    params["segments"] = segs
    if cfg.encoder is not None:
        eseg = Segment(kind="attn", n_layers=cfg.encoder.n_layers)
        eks = jax.random.split(ks[-2], cfg.encoder.n_layers)
        params["encoder"] = jax.vmap(
            lambda k: _init_layer(cfg, eseg, k))(eks)
        params["enc_final_norm"] = jnp.zeros((cfg.d_model,))
        # decoder cross-attention lives beside each decoder layer
        xsegs = []
        for i, seg in enumerate(cfg.resolved_segments):
            lks = jax.random.split(jax.random.fold_in(ks[-1], i),
                                   seg.n_layers)
            xsegs.append(jax.vmap(
                lambda k: _init_layer(cfg, seg, k, cross_attention=True))(lks))
        params["segments"] = xsegs
    if cfg.mtp:
        params["mtp_head"] = dense_init(ks[-3], (cfg.d_model, cfg.vocab_size))
    return jax.tree.map(lambda x: x.astype(dt), params)


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ======================================================================= #
# Attention sub-blocks
# ======================================================================= #
def _gqa_qkv(p: dict, x: jax.Array, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"] + (p["bq"] if "bq" in p else 0.0)
    k = x @ p["wk"] + (p["bk"] if "bk" in p else 0.0)
    v = x @ p["wv"] + (p["bv"] if "bv" in p else 0.0)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def cross_kv(p: dict, enc_out: jax.Array, cfg: ModelConfig):
    """Project encoder output to cross-attention K/V (no RoPE)."""
    B, F, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ p["wk"] + (p["bk"] if "bk" in p else 0.0)).reshape(
        B, F, cfg.n_kv_heads, hd)
    v = (enc_out @ p["wv"] + (p["bv"] if "bv" in p else 0.0)).reshape(
        B, F, cfg.n_kv_heads, hd)
    return k, v


def _gqa_full(p, x, cfg: ModelConfig, positions, window, causal=True,
              kv_override=None):
    """Training/prefill GQA. kv_override: precomputed (k, v) — used by
    cross-attention, where keys come from the encoder. Returns
    (out, (k, v))."""
    B, S, _ = x.shape
    q, k, v = _gqa_qkv(p, x, cfg, positions)
    if kv_override is not None:
        k, v = kv_override
    pos1 = positions if positions.ndim == 1 else positions[0]
    kpos = jnp.arange(k.shape[1]) if kv_override is not None else pos1
    o = attention_prefill(q, k, v, pos1, kpos, window=window,
                          softcap=cfg.attn_logit_softcap, causal=causal)
    return o.reshape(B, S, -1) @ p["wo"], (k, v)


def _gqa_step(p, x, cfg: ModelConfig, cache_k, cache_v, pos, window):
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    positions = jnp.full((B, 1), pos)
    q, k, v = _gqa_qkv(p, x, cfg, positions)
    # Align fresh K/V with the cache layout BEFORE the in-place update —
    # otherwise GSPMD replicates the whole cache to reshard (see ctx).
    k, v = constrain_kv(k), constrain_kv(v)
    cache_k = cache_update(cache_k, k, pos, window)
    cache_v = cache_update(cache_v, v, pos, window)
    o = attention_decode(q, cache_k, cache_v, pos, window=window,
                         softcap=cfg.attn_logit_softcap)
    return o.reshape(B, 1, -1) @ p["wo"], cache_k, cache_v


def _seg_window(cfg: ModelConfig, seg: Segment):
    if seg.full_attention:
        return None
    return seg.sliding_window or cfg.sliding_window


# ======================================================================= #
# Layer application (one scanned step per segment kind)
# ======================================================================= #
def _apply_layer_train(cfg: ModelConfig, seg: Segment, lp: dict,
                       x, positions, enc_out=None):
    x = constrain_batch(x)        # GSPMD hint: batch stays data-parallel
    aux = jnp.zeros((), jnp.float32)
    window = _seg_window(cfg, seg)
    if seg.kind == "rwkv":
        o, _ = rwkv_time_mix(lp["tm"], rmsnorm(x, lp["norm1"], cfg.norm_eps),
                             cfg.resolved_head_dim)
        x = x + o
        o, _ = rwkv_channel_mix(lp["cm"],
                                rmsnorm(x, lp["norm2"], cfg.norm_eps))
        return x + o, aux, None

    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    if "mla" in lp:
        o, _ = mla_prefill(lp["mla"], h, cfg.n_heads, cfg.mla, positions,
                           cfg.rope_theta)
    else:
        o, _ = _gqa_full(lp["attn"], h, cfg, positions, window)
    if seg.kind == "hybrid":
        s, _ = ssm_forward(lp["ssm"], h, cfg.ssm)
        o = jnp.exp(lp["gate_attn"]) * o + jnp.exp(lp["gate_ssm"]) * s
    x = x + o
    if enc_out is not None and "xattn" in lp:
        hx = rmsnorm(x, lp["norm_x"], cfg.norm_eps)
        o, _ = _gqa_full(lp["xattn"], hx, cfg, positions, None,
                         causal=False,
                         kv_override=cross_kv(lp["xattn"], enc_out, cfg))
        x = x + o
    h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    picks = None
    if seg.kind == "moe":
        o, moe_aux = _moe_block(lp["moe"], h2, cfg)
        aux = aux + moe_aux["load_balance"] + moe_aux["router_z"]
        picks = moe_aux.get("picks")
    else:
        o = apply_mlp(lp["mlp"], h2, cfg.mlp)
    return x + o, aux, picks


def _moe_block(p, h, cfg: ModelConfig):
    """Routed experts: expert-parallel all-to-all when the launcher has
    declared an EP axis and the expert count divides it (softmax routers
    holding every expert), else the dropless held-expert layer."""
    ep = ep_axis()
    if (ep is not None and cfg.moe.scoring == "softmax"
            and cfg.moe.held is None):
        dp_axes, name, size, mesh = ep
        if cfg.moe.n_experts % size == 0 and h.shape[1] > 1:
            return apply_moe_ep(p, h, cfg.moe, cfg.mlp, dp_axes, name, size,
                                mesh)
    return apply_moe(p, h, cfg.moe, cfg.mlp)


def _init_layer_cache(cfg: ModelConfig, seg: Segment, B: int, max_seq: int,
                      dt) -> dict:
    hd = cfg.resolved_head_dim
    window = _seg_window(cfg, seg)
    slots = min(max_seq, window) if window else max_seq
    c: dict = {}
    if seg.kind == "rwkv":
        H = cfg.d_model // hd
        return {"tm_x": jnp.zeros((B, cfg.d_model), dt),
                "cm_x": jnp.zeros((B, cfg.d_model), dt),
                "s": jnp.zeros((B, H, hd, hd), dt)}
    if cfg.mla is not None and seg.kind in ("attn", "moe"):
        c["c_kv"] = jnp.zeros((B, max_seq, cfg.mla.kv_lora_rank), dt)
        c["k_rope"] = jnp.zeros((B, max_seq, cfg.mla.rope_head_dim), dt)
    else:
        c["k"] = jnp.zeros((B, slots, cfg.n_kv_heads, hd), dt)
        c["v"] = jnp.zeros((B, slots, cfg.n_kv_heads, hd), dt)
    if seg.kind == "hybrid":
        d_inner = cfg.ssm.expand * cfg.d_model
        H = d_inner // cfg.ssm.head_dim
        c["ssm_s"] = jnp.zeros((B, H, cfg.ssm.state_dim, cfg.ssm.head_dim),
                               dt)
        c["conv_tail"] = jnp.zeros((B, CONV_K - 1, d_inner), dt)
    return c


def _apply_layer_prefill(cfg: ModelConfig, seg: Segment, lp: dict, x,
                         positions, max_seq: int, enc_out=None):
    """Returns (x, cache_entry). Caches are padded to max_seq slots."""
    x = constrain_batch(x)
    B, S, _ = x.shape
    window = _seg_window(cfg, seg)
    dt = x.dtype
    cache = _init_layer_cache(cfg, seg, B, max_seq, dt)
    aux = jnp.zeros((), jnp.float32)

    if seg.kind == "rwkv":
        h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
        o, (tm_x, s) = rwkv_time_mix(lp["tm"], h, cfg.resolved_head_dim)
        x = x + o
        h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
        o, cm_x = rwkv_channel_mix(lp["cm"], h2)
        cache.update(tm_x=tm_x, cm_x=cm_x, s=s)
        return x + o, cache, aux

    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    if "mla" in lp:
        o, (c_kv, k_rope) = mla_prefill(lp["mla"], h, cfg.n_heads, cfg.mla,
                                        positions, cfg.rope_theta)
        cache["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(dt), 0, axis=1)
        cache["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(dt), 0, axis=1)
    else:
        o, (k, v) = _gqa_full(lp["attn"], h, cfg, positions, window)
        slots = cache["k"].shape[1]
        if window and S > slots:
            # keep the last `window` tokens, ring-aligned
            tail_k, tail_v = k[:, -slots:], v[:, -slots:]
            start = (S - slots) % slots
            roll = lambda z: jnp.roll(z, start, axis=1)
            cache["k"], cache["v"] = roll(tail_k), roll(tail_v)
        else:
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(dt), 0, axis=1)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(dt), 0, axis=1)
    if seg.kind == "hybrid":
        s_out, (ssm_s, tail) = ssm_forward(lp["ssm"], h, cfg.ssm)
        o = jnp.exp(lp["gate_attn"]) * o + jnp.exp(lp["gate_ssm"]) * s_out
        cache.update(ssm_s=ssm_s, conv_tail=tail)
    x = x + o
    if enc_out is not None and "xattn" in lp:
        hx = rmsnorm(x, lp["norm_x"], cfg.norm_eps)
        xk, xv = cross_kv(lp["xattn"], enc_out, cfg)
        o, _ = _gqa_full(lp["xattn"], hx, cfg, positions, None,
                         causal=False, kv_override=(xk, xv))
        x = x + o
        cache["xk"], cache["xv"] = xk, xv   # reused every decode step
    h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    if seg.kind == "moe":
        o, moe_aux = _moe_block(lp["moe"], h2, cfg)
        aux = aux + moe_aux["load_balance"] + moe_aux["router_z"]
    else:
        o = apply_mlp(lp["mlp"], h2, cfg.mlp)
    return x + o, cache, aux


def _apply_layer_decode(cfg: ModelConfig, seg: Segment, lp: dict, x, cache,
                        pos, enc_kv=None):
    window = _seg_window(cfg, seg)
    if seg.kind == "rwkv":
        h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
        o, (tm_x, s) = rwkv_time_mix_step(
            lp["tm"], h[:, 0], cache["tm_x"], cache["s"],
            cfg.resolved_head_dim)
        x = x + o[:, None, :]
        h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
        o2, cm_x = rwkv_channel_mix(lp["cm"], h2, x_prev=cache["cm_x"])
        cache = dict(cache, tm_x=tm_x, cm_x=cm_x, s=s)
        return x + o2, cache

    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    if "mla" in lp:
        o, (c_kv, k_rope) = mla_decode(
            lp["mla"], h, (cache["c_kv"], cache["k_rope"]), pos,
            cfg.n_heads, cfg.mla, cfg.rope_theta)
        cache = dict(cache, c_kv=c_kv, k_rope=k_rope)
    else:
        o, ck, cv = _gqa_step(lp["attn"], h, cfg, cache["k"], cache["v"],
                              pos, window)
        cache = dict(cache, k=ck, v=cv)
    if seg.kind == "hybrid":
        s_out, (ssm_s, tail) = ssm_step(lp["ssm"], h, cfg.ssm,
                                        cache["ssm_s"], cache["conv_tail"])
        o = jnp.exp(lp["gate_attn"]) * o + jnp.exp(lp["gate_ssm"]) * s_out
        cache = dict(cache, ssm_s=ssm_s, conv_tail=tail)
    x = x + o
    if "xattn" in lp and "xk" in cache:
        hx = rmsnorm(x, lp["norm_x"], cfg.norm_eps)
        B = hx.shape[0]
        hd = cfg.resolved_head_dim
        q = (hx @ lp["xattn"]["wq"]
             + (lp["xattn"]["bq"] if "bq" in lp["xattn"] else 0.0)
             ).reshape(B, 1, cfg.n_heads, hd)
        o = attention_decode(q, cache["xk"], cache["xv"],
                             jnp.asarray(cache["xk"].shape[1] - 1))
        x = x + o.reshape(B, 1, -1) @ lp["xattn"]["wo"]
    h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    if seg.kind == "moe":
        o, _ = apply_moe(lp["moe"], h2, cfg.moe, cfg.mlp)
    else:
        o = apply_mlp(lp["mlp"], h2, cfg.mlp)
    return x + o, cache


# ======================================================================= #
# Top-level model API
# ======================================================================= #
def _sinusoidal(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _embed(cfg: ModelConfig, params, tokens, prefix_embeds=None,
           pos_offset=0):
    """tokens: (B, S_text); prefix_embeds: (B, P, d) stub modality embeds.
    Returns (x (B, S, d), positions (S,))."""
    x = params["embed"][tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = pos_offset + jnp.arange(S)
    if cfg.pos_emb == "sinusoidal":
        x = x + _sinusoidal(positions, cfg.d_model)[None].astype(x.dtype)
    return constrain_batch(x), positions


def _logits(cfg: ModelConfig, params, x):
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


def _scan_segments(cfg: ModelConfig, params, x, positions, mode: str,
                   caches=None, pos=None, max_seq=None, enc_out=None):
    """Run every segment with lax.scan over its stacked layers. Returns
    (x, aux, per segment: its caches, or in training each MoE segment's
    token-picks per layer and held expert)."""
    # zeros_like inherits x's varying mesh axes, so the scan carry keeps
    # one type when this runs inside a shard_map body.
    aux_total = jnp.zeros_like(x, shape=(), dtype=jnp.float32)
    new_caches = []
    for i, seg in enumerate(cfg.resolved_segments):
        sp = params["segments"][i]
        if mode == "train":
            def body(carry, lp, seg=seg):
                h, aux = carry
                h, a, picks = _apply_layer_train(cfg, seg, lp, h, positions,
                                                 enc_out=enc_out)
                return (h, aux + a), picks
            if cfg.remat:
                body = jax.checkpoint(body,
                                      policy=jax.checkpoint_policies.nothing_saveable)
            (x, aux_total), picks = jax.lax.scan(body, (x, aux_total), sp,
                                                 unroll=cfg.scan_unroll)
            if picks is not None:
                new_caches.append(picks)
        elif mode == "prefill":
            def body(carry, lp, seg=seg):
                h, aux = carry
                h, cache, a = _apply_layer_prefill(
                    cfg, seg, lp, h, positions, max_seq, enc_out=enc_out)
                return (h, aux + a), cache
            (x, aux_total), cache = jax.lax.scan(body, (x, aux_total), sp,
                                                 unroll=cfg.scan_unroll)
            new_caches.append(cache)
        elif mode == "decode":
            def body(h, xs, seg=seg):
                lp, cache = xs
                h, cache = _apply_layer_decode(cfg, seg, lp, h, cache, pos)
                return h, cache
            x, cache = jax.lax.scan(body, x, (sp, caches[i]),
                                    unroll=cfg.scan_unroll)
            new_caches.append(cache)
        else:
            raise ValueError(mode)
    return x, aux_total, new_caches


def encoder_forward(cfg: ModelConfig, params, enc_embeds):
    """Bidirectional encoder over stubbed frame embeddings (B, F, d)."""
    B, F, _ = enc_embeds.shape
    positions = jnp.arange(F)
    x = enc_embeds
    if cfg.pos_emb == "sinusoidal":
        x = x + _sinusoidal(positions, cfg.d_model)[None].astype(x.dtype)
    seg = Segment(kind="attn", n_layers=cfg.encoder.n_layers)

    def body(h, lp):
        hn = rmsnorm(h, lp["norm1"], cfg.norm_eps)
        o, _ = _gqa_full(lp["attn"], hn, cfg, positions, None, causal=False)
        h = h + o
        h2 = rmsnorm(h, lp["norm2"], cfg.norm_eps)
        return h + apply_mlp(lp["mlp"], h2, cfg.mlp), None

    x, _ = jax.lax.scan(body, x, params["encoder"], unroll=cfg.scan_unroll)
    return rmsnorm(x, params["enc_final_norm"], cfg.norm_eps)


def forward_train(cfg: ModelConfig, params, tokens, prefix_embeds=None,
                  enc_embeds=None):
    """Full-sequence forward. Returns (logits (B,S,V), aux dict): the
    summed router losses `moe_aux` and, with MoE layers, `moe_picks`."""
    enc_out = None
    if cfg.encoder is not None:
        assert enc_embeds is not None, "enc-dec model needs encoder embeds"
        enc_out = encoder_forward(cfg, params, enc_embeds)
    x, positions = _embed(cfg, params, tokens, prefix_embeds)
    x, aux, picks = _scan_segments(cfg, params, x, positions, "train",
                                   enc_out=enc_out)
    out = {"moe_aux": aux}
    if picks:           # (MoE layers, held experts) token-picks
        out["moe_picks"] = jnp.concatenate(picks)
    if cfg.mtp and "mtp_head" in params:
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        out["mtp_logits"] = h @ params["mtp_head"]
    return _logits(cfg, params, x), out


def prefill(cfg: ModelConfig, params, tokens, max_seq: int,
            prefix_embeds=None, enc_embeds=None):
    """Process the prompt, build the decode cache.

    Returns (last-position logits (B, V), cache dict)."""
    enc_out = None
    if cfg.encoder is not None:
        enc_out = encoder_forward(cfg, params, enc_embeds)
    x, positions = _embed(cfg, params, tokens, prefix_embeds)
    S = x.shape[1]
    x, _, caches = _scan_segments(cfg, params, x, positions, "prefill",
                                  max_seq=max_seq, enc_out=enc_out)
    logits = _logits(cfg, params, x[:, -1:, :])[:, 0]
    cache = {"segments": caches, "pos": jnp.asarray(S, jnp.int32)}
    return logits, cache


def decode_step(cfg: ModelConfig, params, token, cache):
    """One decode step. token: (B, 1) int32. Returns (logits (B,V), cache)."""
    pos = cache["pos"]
    x, _ = _embed(cfg, params, token, pos_offset=pos)
    x, _, new_caches = _scan_segments(cfg, params, x, None, "decode",
                                      caches=cache["segments"], pos=pos)
    logits = _logits(cfg, params, x)[:, 0]
    return logits, {"segments": new_caches, "pos": pos + 1}


def init_decode_cache(cfg: ModelConfig, params, B: int, max_seq: int,
                      enc_embeds=None, prompt=None, prefix_embeds=None):
    """Convenience: prefill from a prompt (or a single BOS token)."""
    if prompt is None:
        prompt = jnp.zeros((B, 1), jnp.int32)
    return prefill(cfg, params, prompt, max_seq, prefix_embeds=prefix_embeds,
                   enc_embeds=enc_embeds)
