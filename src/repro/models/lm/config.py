"""Architecture configuration for the assigned model families.

One `ModelConfig` describes any of the 10 assigned architectures: dense
GQA/MQA decoders, MoE (top-k routed + shared experts, optionally MLA
attention), attention-free RWKV6, hybrid attention+SSM (Hymba), enc-dec
audio (Whisper backbone), and VLM (decoder backbone + stubbed vision
embeddings).

A model is a sequence of *segments*: contiguous runs of identical layers
that are stacked and scanned (`jax.lax.scan`) so an 80-layer config traces
a single layer per segment. Segment kinds:
  "attn"   — attention + dense MLP
  "moe"    — attention + routed-expert MLP (+ shared experts)
  "rwkv"   — RWKV6 time-mix + channel-mix (attention-free)
  "hybrid" — parallel attention + SSD/Mamba heads, dense MLP
"""
from __future__ import annotations

import dataclasses
from typing import Literal


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed experts. `scoring` "softmax" is the switch-style router
    (balance loss plus z-loss); "sigmoid" is DeepSeek-V3's (noaux_tc):
    sigmoid scores, a selection bias that only picks the experts, gates
    normalised over the picks and scaled by `routed_scale`, and the
    sequence-wise balance loss with `router_aux_coef` as its alpha.
    `held` names the experts this chip holds under expert parallelism
    (None: all of them); the router still scores all `n_experts`."""
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0           # shared (always-on) experts, DeepSeek-style
    capacity_factor: float = 2.0  # the all-to-all path's buffers only
    router_aux_coef: float = 0.01
    scoring: Literal["softmax", "sigmoid"] = "softmax"
    routed_scale: float = 1.0
    held: tuple[int, ...] | None = None

    @property
    def held_ids(self) -> tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.held is None \
            else tuple(self.held)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 multi-head latent attention. `q_lora_rank` None makes
    the query one direct projection (DeepSeek-V2-Lite, Moonlight)."""
    q_lora_rank: int | None = 1536
    kv_lora_rank: int = 512
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """SSD (Mamba-2 style) heads for hybrid blocks."""
    state_dim: int = 16
    expand: int = 2
    head_dim: int = 64
    dt_rank: int = 64


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: Literal["attn", "moe", "rwkv", "hybrid"]
    n_layers: int
    # Per-segment attention window override (None = config default).
    sliding_window: int | None = None
    full_attention: bool = False   # force full attention in this segment


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec models (Whisper). Frontend is a stub:
    inputs arrive as precomputed frame embeddings (B, n_frames, d_model)."""
    n_layers: int
    n_frames: int = 1500          # Whisper: 30 s audio -> 1500 frames


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: Literal["dense", "moe", "ssm", "hybrid", "audio", "vlm"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None           # default d_model // n_heads
    segments: tuple[Segment, ...] = ()    # default: one "attn" run
    # Attention details.
    qkv_bias: bool = False
    # rope_theta = 0 disables RoPE (Whisper-style absolute embeddings).
    rope_theta: float = 10000.0
    pos_emb: Literal["rope", "sinusoidal"] = "rope"
    sliding_window: int | None = None     # None = full causal
    attn_logit_softcap: float | None = None
    # MLP.
    mlp: Literal["swiglu", "geglu", "gelu"] = "swiglu"
    # Optional sub-configs.
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    encoder: EncoderConfig | None = None
    # VLM / audio stub frontend: number of prefix embedding positions the
    # stubbed modality encoder produces (0 = pure text).
    n_prefix_tokens: int = 0
    # Misc.
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # Multi-token prediction (DeepSeek-V3 MTP) — extra next-next-token head.
    mtp: bool = False
    # Activation-checkpoint each scanned layer during training.
    remat: bool = False
    # Unroll layer scans (analysis/calibration only — exact HLO costs).
    scan_unroll: bool = False
    # Citation for the exact configuration (model card / paper).
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def resolved_segments(self) -> tuple[Segment, ...]:
        if self.segments:
            return self.segments
        kind = {"dense": "attn", "vlm": "attn", "audio": "attn",
                "moe": "moe", "ssm": "rwkv", "hybrid": "hybrid"}[self.arch_type]
        return (Segment(kind=kind, n_layers=self.n_layers),)

    @property
    def attention_free(self) -> bool:
        return all(s.kind == "rwkv" for s in self.resolved_segments)

    @property
    def supports_long_context(self) -> bool:
        """long_500k eligibility: the arch must have *some* sub-quadratic /
        bounded-cache token mixing — SSM or RWKV state, or sliding-window
        attention on its (non-anchor) attention segments. A handful of
        full-attention anchor layers (Hymba-style) keep decode O(S) and the
        cache linear, so they do not disqualify; an arch whose *only*
        mechanism is full attention does."""
        has_state = any(s.kind in ("rwkv", "hybrid")
                        for s in self.resolved_segments)
        windowed = all(
            s.full_attention or s.sliding_window or self.sliding_window
            for s in self.resolved_segments if s.kind in ("attn", "moe"))
        any_attn = any(s.kind in ("attn", "moe")
                       for s in self.resolved_segments)
        return has_state or (any_attn and windowed)

    def share(self, moe_layers: int, held: tuple[int, ...],
              vocab_size: int) -> "ModelConfig":
        """One chip's share of a deployment that divides each layer over
        chips by experts (the `model-configs` guide, section 4): the
        leading dense layers, the first `moe_layers` MoE layers (the rest
        would be further pipeline stages), the routed experts `held` of
        each MoE layer, and the first `vocab_size` ids of the vocabulary
        for the embedding and the head. Every width stays as published,
        and so do the router's outputs and its experts per token."""
        segs, seen = [], 0
        for s in self.resolved_segments:
            if s.kind != "moe":
                if seen:
                    break
                segs.append(s)
                continue
            take = min(s.n_layers, moe_layers - seen)
            if take <= 0:
                break
            segs.append(dataclasses.replace(s, n_layers=take))
            seen += take
        if seen != moe_layers or any(not 0 <= e < self.moe.n_experts
                                     for e in held):
            raise ValueError(f"{self.name}: no share of {moe_layers} MoE "
                             f"layers holding experts {held}")
        return dataclasses.replace(
            self, name=f"{self.name}-share", segments=tuple(segs),
            n_layers=sum(s.n_layers for s in segs),
            vocab_size=int(vocab_size),
            moe=dataclasses.replace(self.moe, held=tuple(held)))

    def reduced(self, n_layers: int = 2, d_model: int = 256,
                n_experts: int = 4) -> "ModelConfig":
        """Smoke-test variant: same family, tiny dimensions."""
        hd = 64
        heads = max(2, d_model // hd)
        kv = max(1, min(self.n_kv_heads, heads))
        segs = []
        total = 0
        for s in self.resolved_segments:
            if total >= n_layers:
                break
            take = min(s.n_layers, n_layers - total)
            segs.append(dataclasses.replace(
                s, n_layers=take,
                sliding_window=min(s.sliding_window, 128)
                if s.sliding_window else None))
            total += take
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe, n_experts=min(n_experts, self.moe.n_experts),
                top_k=min(self.moe.top_k, 2),
                d_ff_expert=d_model, n_shared=min(self.moe.n_shared, 1),
                capacity_factor=8.0, held=None)
        mla = None
        if self.mla is not None:
            mla = MLAConfig(q_lora_rank=None if self.mla.q_lora_rank is None
                            else 64, kv_lora_rank=32,
                            rope_head_dim=32, nope_head_dim=hd, v_head_dim=hd)
        ssm = None
        if self.ssm is not None:
            ssm = dataclasses.replace(self.ssm, head_dim=hd, dt_rank=16)
        enc = None
        if self.encoder is not None:
            enc = EncoderConfig(n_layers=min(2, self.encoder.n_layers),
                                n_frames=64)
        return dataclasses.replace(
            self, name=self.name + "-smoke", n_layers=total or n_layers,
            d_model=d_model, n_heads=heads, n_kv_heads=kv, head_dim=hd,
            d_ff=2 * d_model, vocab_size=min(self.vocab_size, 512),
            segments=tuple(segs), moe=moe, mla=mla, ssm=ssm, encoder=enc,
            sliding_window=min(self.sliding_window, 128)
            if self.sliding_window else None,
            n_prefix_tokens=min(self.n_prefix_tokens, 16),
            dtype="float32",
        )
