"""Workload abstraction — what the constellation actually trains.

The space-ification framework (selection, timing, aggregation, the event
loops) is task-agnostic; everything task-specific is bundled here. A
`Workload` carries:

  * `init_fn(rng) -> params` and `loss_fn(params, xb, yb) -> scalar` —
    the model and its per-batch data loss (the proximal term is added by
    `repro.core.client`);
  * `eval_fn(params, x, y, n_valid) -> scalar` — weighted metric over
    stacked eval clients (accuracy for classification, next-token
    accuracy for LM fine-tuning);
  * a batch schema (`sample_shape`, `sample_dtype`) plus
    `make_data(n_clients, seed) -> FederatedDataset` producing shards in
    that schema;
  * a derived cost model: `model_bytes` and `epoch_mflops` computed from
    the parameter tree (via `jax.eval_shape`) and the architecture config
    (FLOPs-per-sample formula), not hardcoded constants.
    `HardwareModel.for_workload` turns these into comms/compute times, so
    round durations and `RoundRecord.comms_bytes` scale with the actual
    model being federated.

The cost model distinguishes *total* from *activated* parameters: wire
bytes are paid on every parameter in the tree (`n_params` — a satellite
uploads all experts), but per-token FLOPs only on the parameters a token
actually multiplies (`active_params`). For dense nets the two coincide;
for a sparse MoE only `top_k` of `n_experts` routed experts fire per
token, and an untied embedding table is a gather (one row per token),
not a matmul. `lm_inactive_params` is the per-architecture formula —
it walks `ModelConfig.resolved_segments`, so mixed dense/MoE stacks
(DeepSeek-style) price each segment by its kind.

`WORKLOADS` registers the built-in scenarios:

  * `femnist_mlp` — the paper's sweep model. Its cost numbers are pinned
    to the paper's section-5 constants (186 KB / 98 MFLOP), which keeps
    the default simulation path bitwise identical to the seed.
  * `femnist_cnn` — the paper's headline 47k-parameter CNN, cost model
    derived from its conv/dense dims.
  * `lm_tiny`   — a small `repro.models.lm` transformer fine-tuning on
    federated token shards (`repro.data.tokens.federated_token_shards`),
    the on-ramp for pricing the assigned LM architectures as
    constellation clients (`lm_workload` builds one for any ModelConfig).
  * `lm_moe_tiny` / `lm_rwkv6_tiny` / `lm_hybrid_tiny` — reduced variants
    of the assigned architecture families (DeepSeek-V3 MoE+MLA, RWKV6,
    Hymba-style hybrid) as sweepable constellation workloads. The MoE
    entry is the round-duration vs model-bytes crossover axis: all
    experts ride the wire, only `top_k` of them train per token.
  * `moonlight_16b_a3b` — Moonlight-16B-A3B at its published widths,
    cut to one chip's share of an expert-parallel deployment
    (`ModelConfig.share`): 669 M parameters, whose round's clients do
    not fit the device together, so the host loop streams them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client import classification_loss, evaluate
from repro.data.femnist import IMG, synth_femnist
from repro.data.tokens import federated_token_shards
from repro.orbits import constants as C


EXECUTION_MODES = ("host", "mesh")


def validate_execution(execution: str) -> str:
    """The one validator for execution modes — `Workload.with_execution`
    and `ConstellationSim` both route here, so the accepted set and the
    error message cannot drift apart."""
    if execution not in EXECUTION_MODES:
        raise ValueError(f"unknown execution mode {execution!r}; "
                         f"expected one of {EXECUTION_MODES}")
    return execution


@dataclasses.dataclass(frozen=True)
class Workload:
    """A federated training task: model + loss + data schema + cost model."""

    name: str
    init_fn: Callable                    # rng -> params pytree
    loss_fn: Callable                    # (params, xb, yb) -> scalar
    eval_fn: Callable                    # (params, x, y, n_valid) -> scalar
    make_data: Callable                  # (n_clients, seed=...) -> dataset
    sample_shape: tuple[int, ...]        # batch schema: per-sample x shape
    sample_dtype: str = "float32"        #   ... and dtype
    # --- execution descriptor -------------------------------------------
    # How the engine runs this workload's client updates:
    #   "host" — the reference path: one jitted vmap over stacked clients,
    #            aggregation as a host-side weighted reduction;
    #   "mesh" — cluster-as-collective: clients are pod slots on a mesh
    #            axis, local SGD runs inside shard_map and aggregation is
    #            a participation-masked psum (`launch.fl_round`).
    # `ConstellationSim(..., execution=...)` overrides per run.
    execution: str = "host"
    # `loss_fn` returns (loss, stats): a dict of per-step counters (MoE
    # routing, say) that the host loop's client program sums over its
    # live steps and returns; they reach the tracer's counters under a
    # syncing tracer. The mesh step and the batched sweep carry none.
    loss_has_aux: bool = False
    mesh_axis: str = "pod"               # mesh axis carrying client pods
    # Batch-key ranks for the launch-style dict-batch contract (leading
    # dim sharded over `mesh_axis`); None = the engine's (x, y) schema.
    mesh_batch_dims: dict[str, int] | None = None
    # --- cost model -----------------------------------------------------
    # FLOPs for one training sample (fwd+bwd). Either an explicit number
    # computed from the architecture dims, or a per-parameter multiplier
    # applied to the *activated* parameter count (6 for dense nets:
    # 2 FLOP/MAC forward x3 for backward; 6*tokens for transformers).
    flops_per_sample: float | None = None
    train_flops_per_param: float | None = None
    # Parameters in the tree that a token never multiplies: routed MoE
    # experts beyond top_k, an untied embedding table (gather, not
    # matmul). They cost wire bytes (`model_bytes`) but no FLOPs —
    # `active_params = n_params - inactive_params` is what
    # `train_flops_per_param` prices. 0 for dense nets.
    inactive_params: int = 0
    samples_per_epoch: int = 275         # nominal local-epoch size
    # Full-precision wire width. ONE source of truth for the default —
    # `repro.orbits.constants.BYTES_PER_PARAM` (f32), shared with
    # `HardwareModel`/`lm_hardware_model`; `lm_workload` overrides it
    # with the architecture dtype's width, and `model_bytes_override`
    # wins over both (tests/test_codec.py pins the precedence).
    bytes_per_param: int = C.BYTES_PER_PARAM
    # Calibration overrides (paper constants). When set they win over the
    # derived numbers — `femnist_mlp` uses them to stay bitwise identical
    # to the seed's HardwareModel defaults.
    model_bytes_override: int | None = None
    epoch_mflops_override: float | None = None
    # Platform overrides: a workload may pin its own radio/compute instead
    # of the paper's section-5 satellite (e.g. a heavy LM flown on a
    # high-gain bus). `HardwareModel.for_workload` and the benchmark
    # contact-plan cache (`benchmarks.common`) honour these, so cached
    # ConstantRate plans are re-rated per workload.
    link_mbps: float | None = None
    gflops: float | None = None

    # ------------------------------------------------------------------ #
    def with_execution(self, execution: str) -> "Workload":
        """This workload, dispatched to `execution` ("host" | "mesh")."""
        return dataclasses.replace(
            self, execution=validate_execution(execution))

    @functools.cached_property
    def n_params(self) -> int:
        """Parameter count, via shape-only tracing of `init_fn` (no FLOPs)."""
        shapes = jax.eval_shape(self.init_fn, jax.random.PRNGKey(0))
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))

    @property
    def active_params(self) -> int:
        """Parameters a training token actually multiplies — what FLOPs
        are priced on. Equals `n_params` for dense nets; strictly less
        for sparse MoEs (idle experts) and untied embedding gathers."""
        active = self.n_params - self.inactive_params
        if not 0 < active <= self.n_params:
            raise ValueError(
                f"workload {self.name!r}: inactive_params="
                f"{self.inactive_params} leaves no activated parameters "
                f"(n_params={self.n_params})")
        return active

    @property
    def model_bytes(self) -> int:
        """Bytes on the wire for one model transfer — *total* parameters:
        a satellite uploads every expert, activated or not."""
        if self.model_bytes_override is not None:
            return int(self.model_bytes_override)
        return self.n_params * self.bytes_per_param

    @property
    def epoch_mflops(self) -> float:
        """MFLOPs for one local epoch on one client."""
        if self.epoch_mflops_override is not None:
            return float(self.epoch_mflops_override)
        fps = self.flops_per_sample
        if fps is None:
            if self.train_flops_per_param is None:
                raise ValueError(
                    f"workload {self.name!r} has no cost model: set "
                    "flops_per_sample, train_flops_per_param, or overrides")
            fps = self.train_flops_per_param * self.active_params
        return fps * self.samples_per_epoch / 1e6


# ======================================================================= #
# Built-in workloads
# ======================================================================= #
def classification_workload(name: str, init_fn, apply_fn,
                            **cost) -> Workload:
    """Wrap an image-classifier (init, apply) pair — the seed's contract:
    cross-entropy data loss, weighted-accuracy eval, FEMNIST shards."""
    return Workload(
        name=name,
        init_fn=init_fn,
        loss_fn=classification_loss(apply_fn),
        eval_fn=lambda p, x, y, n: evaluate(apply_fn, p, x, y, n),
        make_data=synth_femnist,
        sample_shape=(IMG, IMG, 1),
        sample_dtype="float32",
        **cost,
    )


def _femnist_mlp() -> Workload:
    from repro.models.femnist_mlp import femnist_mlp_apply, femnist_mlp_init
    # Cost pinned to the paper's section-5 constants (186 KB / 98 MFLOP):
    # the derived numbers land within a few percent (46,639 params x 4 B =
    # 182 KB; 6 FLOP/param x ~275 samples = 77 MFLOP) but the pin keeps
    # the default simulation path bitwise identical to the seed.
    return classification_workload(
        "femnist_mlp", femnist_mlp_init, femnist_mlp_apply,
        train_flops_per_param=6.0,
        model_bytes_override=C.MODEL_BYTES,
        epoch_mflops_override=C.EPOCH_MFLOPS,
    )


def _femnist_cnn() -> Workload:
    from repro.models.femnist_cnn import femnist_cnn_apply, femnist_cnn_init
    # Derived cost: conv FLOPs scale with spatial positions, not params.
    # fwd MACs = 28^2*(3*3*1*8) + 14^2*(3*3*8*16) + 784*56 + 56*47
    conv_macs = 28 * 28 * 3 * 3 * 1 * 8 + 14 * 14 * 3 * 3 * 8 * 16
    dense_macs = 7 * 7 * 16 * 56 + 56 * 47
    fwd_flops = 2.0 * (conv_macs + dense_macs)
    return classification_workload(
        "femnist_cnn", femnist_cnn_init, femnist_cnn_apply,
        flops_per_sample=3.0 * fwd_flops,    # fwd + ~2x fwd for backward
    )


def make_lm_evaluate(cfg) -> Callable:
    """Weighted next-token accuracy over stacked eval clients.

    x: (K, N, S+1) int32 token rows; y is ignored (targets are x shifted);
    n_valid: (K,) valid-row counts. Mirrors `client.evaluate`'s contract
    so the engine's padded-eval path works unchanged. Rows are mapped
    over one at a time, so no more than one row's logits are held.
    """
    from repro.models.lm.transformer import forward_train

    @jax.jit
    def lm_evaluate(params, x, y, n_valid):
        del y

        def one(row):
            logits, _ = forward_train(cfg, params, row[None, :-1])
            hit = jnp.argmax(logits[0], axis=-1) == row[1:]
            return jnp.mean(hit.astype(jnp.float32))

        K, N = x.shape[:2]
        correct = jax.lax.map(one, x.reshape((K * N,) + x.shape[2:]))
        mask = (jnp.arange(N)[None, :] < n_valid[:, None]).astype(
            jnp.float32)
        return jnp.sum(correct.reshape(K, N) * mask) / jnp.maximum(
            jnp.sum(mask), 1.0)

    return lm_evaluate


def lm_inactive_params(cfg) -> int:
    """Parameters of a `repro.models.lm` ModelConfig that sit in the tree
    (and on the wire) but that a training token never multiplies.

    The per-architecture formula walks `cfg.resolved_segments`:

      * "attn" / "rwkv" / "hybrid" layers are fully dense — attention,
        time-mix, SSM heads, and MLPs all touch every weight per token;
      * "moe" layers fire only `top_k` of `n_experts` routed experts per
        token (router and shared experts stay dense), so the other
        `n_experts - top_k` expert MLPs are idle FLOP-wise;
      * an untied embedding table is a per-token row *gather*, not a
        matmul (the output head — tied or not — is a real matmul and
        stays active, as does a DeepSeek-style MTP head).

    Mixed stacks (DeepSeek-V3's dense-then-MoE) price each segment by its
    kind. The estimate deliberately ignores capacity-factor token drops —
    6 FLOP/active-param/token is the standard planning number.
    """
    inactive = 0
    if not cfg.tie_embeddings:
        inactive += cfg.vocab_size * cfg.d_model
    if cfg.moe is not None:
        # One routed expert = w1/w2 (+ w3 when the MLP is gated), each
        # (d_model x d_ff_expert) — mirrors models.lm.moe.init_moe.
        mats = 3 if cfg.mlp in ("swiglu", "geglu") else 2
        per_expert = mats * cfg.d_model * cfg.moe.d_ff_expert
        # Of the experts held here, a token fires top_k / n_experts.
        held = len(cfg.moe.held_ids)
        idle = held - min(cfg.moe.top_k, cfg.moe.n_experts) * held \
            / cfg.moe.n_experts
        moe_layers = sum(s.n_layers for s in cfg.resolved_segments
                         if s.kind == "moe")
        inactive += int(round(moe_layers * idle * per_expert))
    return inactive


def routing_stats(aux: dict) -> dict:
    """The held experts' routing counters of one step, from
    `forward_train`'s `moe_picks` (MoE layers x held experts): the
    token-picks that landed on them, the most and the mean on one expert
    (each summed over layers), and the tokens dropped, none in the
    dropless layer."""
    picks = aux["moe_picks"]
    return {"moe.local_picks": jnp.sum(picks),
            "moe.local_picks_max": jnp.sum(jnp.max(picks, axis=-1)),
            "moe.local_picks_mean": jnp.sum(
                jnp.mean(picks.astype(jnp.float32), axis=-1)),
            "moe.dropped": jnp.zeros((), picks.dtype)}


def lm_workload(cfg, *, name: str | None = None, seq_len: int = 32,
                samples_per_client: int = 32, eval_samples: int = 8,
                with_routing_stats: bool = False) -> Workload:
    """Federate any `repro.models.lm` ModelConfig over token shards.

    The cost model is the standard transformer estimate: 6 FLOP per
    *activated* parameter per token (fwd+bwd), (seq_len + 1) tokens per
    sample row. Total parameter count comes from the real parameter tree
    and prices the wire (`model_bytes` at `cfg.dtype` width); the
    activated subset (`lm_inactive_params`) prices compute — for a
    sparse MoE the two diverge, which is exactly the round-duration vs
    model-bytes crossover the sweep explores.
    """
    from repro.models.lm.transformer import init_params
    from repro.train.step import lm_loss

    def loss_fn(params, xb, yb):
        del yb                     # targets are xb shifted by one token
        loss, metrics = lm_loss(cfg, params, {"tokens": xb[:, :-1],
                                              "targets": xb[:, 1:]})
        return (loss, routing_stats(metrics)) if with_routing_stats \
            else loss

    bytes_per_param = jnp.dtype(cfg.dtype).itemsize
    return Workload(
        name=name or f"lm_{cfg.name}",
        init_fn=functools.partial(init_params, cfg),
        loss_fn=loss_fn,
        eval_fn=make_lm_evaluate(cfg),
        make_data=functools.partial(
            federated_token_shards, seq_len=seq_len,
            samples_per_client=samples_per_client, vocab=cfg.vocab_size,
            eval_samples=eval_samples),
        sample_shape=(seq_len + 1,),
        sample_dtype="int32",
        mesh_batch_dims={"tokens": 2},

        train_flops_per_param=6.0 * (seq_len + 1),
        inactive_params=lm_inactive_params(cfg),
        samples_per_epoch=samples_per_client,
        bytes_per_param=int(bytes_per_param),
        loss_has_aux=with_routing_stats,
    )


def _lm_tiny() -> Workload:
    from repro.models.lm.config import ModelConfig
    cfg = ModelConfig(
        name="tiny", arch_type="dense", n_layers=2, d_model=64,
        n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=128, head_dim=32,
        tie_embeddings=True, dtype="float32",
        source="reduced dense decoder for constellation fine-tuning")
    return lm_workload(cfg, name="lm_tiny", seq_len=32,
                       samples_per_client=32, eval_samples=8)


def _lm_moe_tiny() -> Workload:
    """Reduced DeepSeek-V3: 3 dense MLA layers + 1 MoE layer (1 shared +
    8 routed experts, top-2) + MTP head. The crossover workload: every
    expert rides the wire (`model_bytes` counts all 8), but per-token
    FLOPs only touch 2 — small epoch time against large model bytes."""
    from repro.configs import get_config
    cfg = get_config("deepseek-v3-671b").reduced(n_layers=4, n_experts=8)
    return lm_workload(cfg, name="lm_moe_tiny", seq_len=32,
                       samples_per_client=32, eval_samples=8)


def _lm_rwkv6_tiny() -> Workload:
    """Reduced RWKV6 (Finch): 2 attention-free time-mix/channel-mix
    layers. Fully dense per token — only the untied embedding gather
    separates activated from total parameters."""
    from repro.configs import get_config
    return lm_workload(get_config("rwkv6-1.6b").reduced(),
                       name="lm_rwkv6_tiny", seq_len=32,
                       samples_per_client=32, eval_samples=8)


def _lm_hybrid_tiny() -> Workload:
    """Reduced Hymba: 2 hybrid layers (parallel sliding-window attention
    + SSD heads; the first is a full-attention anchor)."""
    from repro.configs import get_config
    return lm_workload(get_config("hymba-1.5b").reduced(),
                       name="lm_hybrid_tiny", seq_len=32,
                       samples_per_client=32, eval_samples=8)


def _moonlight_16b_a3b() -> Workload:
    """Moonlight-16B-A3B at its published widths, cut to one chip's share
    of an 8-chip expert-parallel deployment (`configs.moonlight_16b_a3b`:
    the dense layer and 5 MoE layers, 8 of 64 experts, 20,480 of 163,840
    ids; 669 M parameters), in float32, each layer rematerialised in the
    backward pass, on rows of 4,096 tokens. Its loss returns the held
    experts' routing counters."""
    from repro.configs import get_config
    from repro.configs.moonlight_16b_a3b import CHIP_SHARE
    cfg = dataclasses.replace(
        get_config("moonlight-16b-a3b").share(**CHIP_SHARE), remat=True)
    return lm_workload(cfg, name="moonlight_16b_a3b", seq_len=4096,
                       samples_per_client=6, eval_samples=1,
                       with_routing_stats=True)


# Registry entries are built lazily (constructing the LM workload touches
# the model stack) and cached after first use.
_BUILDERS: dict[str, Callable[[], Workload]] = {
    "femnist_mlp": _femnist_mlp,
    "femnist_cnn": _femnist_cnn,
    "lm_tiny": _lm_tiny,
    "lm_moe_tiny": _lm_moe_tiny,
    "lm_rwkv6_tiny": _lm_rwkv6_tiny,
    "lm_hybrid_tiny": _lm_hybrid_tiny,
    "moonlight_16b_a3b": _moonlight_16b_a3b,
}
_CACHE: dict[str, Workload] = {}


def register_workload(name: str, builder: Callable[[], Workload]) -> None:
    """Add a workload to the registry (idempotent per name)."""
    _BUILDERS[name] = builder
    _CACHE.pop(name, None)


def workload_names() -> list[str]:
    return sorted(_BUILDERS)


def get_workload(workload: str | Workload) -> Workload:
    """Resolve a registry name (or pass a Workload through unchanged)."""
    if isinstance(workload, Workload):
        return workload
    if workload not in _BUILDERS:
        raise KeyError(
            f"unknown workload {workload!r}; registered: {workload_names()}")
    if workload not in _CACHE:
        _CACHE[workload] = _BUILDERS[workload]()
    return _CACHE[workload]
