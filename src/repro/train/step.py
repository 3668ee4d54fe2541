"""Training / prefill / serve steps for the assigned LM architectures.

`make_train_step(cfg)` builds the jit-able training step used both by the
multi-pod dry-run (lower + compile against ShapeDtypeStructs) and the
runnable examples (reduced configs on CPU). The same function body serves
as `ClientUpdate` inner step when an LM is federated across a constellation
(`examples/constellation_llm.py`).

Decode shapes lower `serve_step` — one token against a KV cache — and
prefill shapes lower `prefill_step`, per the brief.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.lm.config import ModelConfig
from repro.models.lm.transformer import decode_step, forward_train, prefill
from repro.optim.adam import adam_init, adam_update

Batch = dict[str, Any]


def _ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """One-hot cross-entropy.

    take_along_axis on a vocab-sharded logits tensor makes GSPMD fall back
    to full-batch gathers (and a scatter in the VJP); the one-hot
    formulation keeps every op elementwise/reduction so the vocab axis
    stays tensor-parallel end to end (MaxText does the same).
    """
    l32 = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(l32, axis=-1, keepdims=True))
    shifted = l32 - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    one_hot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    picked = jnp.sum(one_hot * shifted, axis=-1)
    return jnp.mean(lse - picked)


def lm_loss(cfg: ModelConfig, params, batch: Batch):
    """Next-token CE (+ MoE aux, + MTP head loss when configured).

    batch: {"tokens": (B, S) int32, optional "targets" (B, S), the id
    after each token (without them each token's target is the next
    token, and the last one has none), optional "prefix_embeds"
    (B, P, d), optional "enc_embeds" (B, F, d)}. Prefix positions carry
    no loss.
    """
    tokens = batch["tokens"]
    targets = batch.get("targets")
    logits, aux = forward_train(
        cfg, params, tokens,
        prefix_embeds=batch.get("prefix_embeds"),
        enc_embeds=batch.get("enc_embeds"))
    P = logits.shape[1] - tokens.shape[1]          # prefix length
    text_logits = logits[:, P:, :]
    if targets is None:
        text_logits, targets = text_logits[:, :-1], tokens[:, 1:]
    loss = _ce(text_logits, targets)
    metrics = {"ce": loss}
    if "moe_aux" in aux:
        loss = loss + aux["moe_aux"]
        metrics["moe_aux"] = aux["moe_aux"]
    if "moe_picks" in aux:
        metrics["moe_picks"] = aux["moe_picks"]
    if "mtp_logits" in aux:
        mtp = aux["mtp_logits"][:, P:P + targets.shape[1] - 1, :]
        mtp_loss = _ce(mtp, targets[:, 1:])
        loss = loss + 0.3 * mtp_loss
        metrics["mtp"] = mtp_loss
    metrics["loss"] = loss
    return loss, metrics


def make_train_step(cfg: ModelConfig, lr: float = 3e-4,
                    weight_decay: float = 0.0,
                    remat: bool = True,
                    replicate_weights: bool = False):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    `remat` checkpoints each scanned layer — the standard memory/compute
    trade for long-sequence training (counted in the roofline's
    MODEL_FLOPS / HLO_FLOPs ratio).

    `replicate_weights` is the small-model-on-big-mesh mode (ZeRO-1-style):
    parameters live sharded between steps but are all-gathered ONCE at
    step start and used replicated, making every layer pure data-parallel
    (zero per-layer collectives; the VJP of the constraint all-reduces the
    grads). For models whose bf16 weights fit per chip this beats tensor
    parallelism by orders of magnitude on the collective roofline term —
    rwkv6-1.6b went from a 7.8 s to a ~0.2 s collective term
    (EXPERIMENTS.md §Perf).
    """
    if remat:
        # Per-layer activation checkpointing happens inside the layer scan
        # (transformer._scan_segments); flag it through the config object.
        import dataclasses
        cfg = dataclasses.replace(cfg, remat=True)

    def loss_with_gather(params, batch):
        if replicate_weights:
            from jax.sharding import PartitionSpec as P
            params = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, P(*([None] * x.ndim))), params)
        return lm_loss(cfg, params, batch)

    grad_fn = jax.value_and_grad(loss_with_gather, has_aux=True)

    def train_step(params, opt_state, batch):
        (_, metrics), grads = grad_fn(params, batch)
        params, opt_state = adam_update(params, grads, opt_state, lr=lr,
                                        weight_decay=weight_decay)
        return params, opt_state, metrics

    return train_step


def make_optimizer_state(params):
    return adam_init(params)


def make_prefill_step(cfg: ModelConfig, max_seq: int):
    def prefill_step(params, batch):
        return prefill(cfg, params, batch["tokens"], max_seq,
                       prefix_embeds=batch.get("prefix_embeds"),
                       enc_embeds=batch.get("enc_embeds"))
    return prefill_step


def make_serve_step(cfg: ModelConfig):
    """One decode step: greedy-sample the next token for a whole batch."""
    def serve_step(params, token, cache):
        logits, cache = decode_step(cfg, params, token, cache)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_token, logits, cache
    return serve_step
