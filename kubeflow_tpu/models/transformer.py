"""Decoder-only transformer LM (the flagship workload).

Llama-3-family architecture — RMSNorm pre-norm, rotary positions, grouped-
query flash attention, SwiGLU MLP — written TPU-first:

- Layers are *stacked* (one leading L dim per weight) and iterated with
  ``lax.scan`` (compile time O(1) in depth, FSDP shards every layer
  identically) or, for shallow models, an unrolled Python loop
  (``cfg.scan_layers=False`` — avoids the scan's saved-activation
  stacking, measured ~27% of step time at 3 layers).
- All matmuls run in bfloat16 against float32 master weights held by the
  optimizer; contractions request float32 accumulation on the MXU.
- Sharding is declared as path rules (DP×FSDP×TP out of the box); activations
  get explicit constraints at layer boundaries so GSPMD's decisions stay
  pinned under compiler drift.
- Optional context parallelism routes attention through the ring kernel over
  the ``sequence`` mesh axis (long-context mode, SURVEY.md §5.7).
"""

from __future__ import annotations

import functools
import re
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.observability.tracing import (
    SCOPE_ATTN,
    SCOPE_CAST_WEIGHTS,
    SCOPE_EMBED,
    SCOPE_HEAD,
    SCOPE_HEAD_LOSS,
    SCOPE_LINEAR_ATTN,
    SCOPE_LOOP_NORM,
    SCOPE_MLP,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTE,
    SCOPE_POST_NORM,
    SCOPE_SPARSE_ATTN,
    SCOPE_SPARSE_SELECT,
    scope,
)
from kubeflow_tpu.ops import flash_attention, rms_norm
from kubeflow_tpu.ops.grouped_experts import (
    grouped_expert_mlp,
    grouped_experts_implementation,
)
from kubeflow_tpu.ops.linear_attention import (
    lightning_chunked,
    lightning_slopes,
    lightning_step,
)
from kubeflow_tpu.ops.sparse_attention import (
    SparseSpec,
    attend_span,
    compress_keys,
)
from kubeflow_tpu.ops.rotary import apply_rotary, rotary_frequencies
from kubeflow_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_PIPELINE,
    AXIS_SEQUENCE,
    AXIS_TENSOR,
)
from kubeflow_tpu.parallel.ring_attention import ring_attention
from kubeflow_tpu.parallel.sharding import PartitionRule, path_str


MIXER_LIGHTNING = "lightning-attn"
MIXER_SPARSE = "minicpm4"
MIXER_KINDS = (MIXER_LIGHTNING, MIXER_SPARSE)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14_336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False
    # Attention runs through the sequence-axis ring kernel when True.
    context_parallel: bool = False
    remat: bool = True
    # Mixture-of-Experts FFN (0 = dense). GShard-style top-k routing with a
    # static capacity per expert (dropped tokens ride the residual), expert
    # weights sharded over the mesh's `expert` axis — GSPMD inserts the
    # dispatch/combine all-to-alls from the einsum shardings.
    n_experts: int = 0
    expert_top_k: int = 2
    expert_capacity_factor: float = 1.25
    router_aux_loss: float = 0.01
    # Pipeline parallelism (0 = off): layers split into this many stages
    # over the mesh's `pipeline` axis, GPipe-scheduled with
    # pipeline_microbatches microbatches (parallel/pipeline.py).
    pipeline_stages: int = 0
    pipeline_microbatches: int = 4
    # Attention implementation: None (auto = blockwise flash), "plain",
    # "xla" (kubeflow_tpu.ops.flash_attention's implementation arg) and the
    # kv block width — None picks the per-path measured-best (2048 on the
    # XLA scan, where block_k == seq_len collapses it to one fused block,
    # +14% step throughput on v5e; 1024 tiles on the TPU kernels).
    attn_impl: str | None = None
    attn_block_k: int | None = None
    # jax.checkpoint policy when remat=True: "dots" saves matmul outputs
    # (recompute only elementwise), "none" saves nothing (full recompute,
    # minimum HBM traffic), "dots_batched" additionally saves batched dots,
    # "llm" saves exactly the tensors a decoder block's backward reuses
    # most per byte (gate/up projections + pre-wo attention context) and
    # recomputes the cheap rest — measured the best time×memory point for
    # deep models on one chip.
    remat_policy: str = "dots"
    # Iterate layers with lax.scan (O(1) compile in depth) or a Python
    # loop. Scan stacks every saved activation through dynamic-update-
    # slices — measured ~27% of step time at 3 layers — so shallow models
    # should unroll; deep ones need scan for compile time.
    scan_layers: bool = True
    # Compute the LM head + cross entropy in this many row chunks under
    # jax.checkpoint (0 = unchunked): the full [tokens, vocab] fp32 logits
    # (>1GB at 8k tokens × 32k vocab) never materialize — backward
    # recomputes each chunk's logits. Training-loss path only; apply()
    # still returns full logits for serving.
    loss_chunks: int = 0
    # Chunked layer iteration: scan over n_layers/scan_group_size groups,
    # unrolling the layers inside each group. The remat boundary moves to
    # the group, so the only activations the scan stacks are the group
    # inputs ([G, B, T, D]) instead of every per-layer saved dot —
    # compile stays O(G) while the dynamic-update-slice stacking cost
    # drops by the group factor. 1 = plain per-layer scan.
    scan_group_size: int = 1
    # Hybrid decoder: one mixer kind per layer, MIXER_LIGHTNING (decayed
    # linear attention: a float32 state per row, no K/V) or MIXER_SPARSE
    # (softmax attention without rotary over selected blocks of its K/V,
    # by a score over compressed keys). Empty = every layer the rotary
    # GQA attention above, stacked and scanned; set, the layers are a
    # list of per-layer trees (the two kinds' shapes differ) and the loop
    # over them is unrolled. Both kinds norm q and k per head and gate
    # their output; ``rope_theta`` is the lightning layers'.
    mixer_types: tuple[str, ...] = ()
    # muP: the embedding times ``embed_scale``, each residual branch times
    # ``residual_scale`` (scale_depth / sqrt(published depth): it does not
    # follow a depth cut), the final hidden times ``head_scale`` before
    # the head (dim_model_base / d_model).
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    head_scale: float = 1.0
    # The sparse layers' selection (ops/sparse_attention.py:SparseSpec):
    # compressed-key window and stride, block, blocks read per query,
    # blocks always read from the start, local window, and the context
    # length up to which a query reads everything.
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192
    # Tokens per chunk of the lightning layers' prefill form.
    lightning_chunk: int = 128
    # Looped stack: the ``n_layers`` layers run ``n_passes`` times a token
    # with the SAME weights; the final norm closes every pass and its
    # output is the next pass's input; every (pass, layer) holds K/V of
    # its own (``cache_layers``). The logits are the last pass's: an exit
    # gate (a d_model -> 1 map on a pass's normed hidden, in the tree as
    # ``exit_gate``) would end a token at the first pass whose cumulative
    # exit probability reaches ``exit_threshold``; at 1.0 that is always
    # the last, the forward does not evaluate the gate, and anything
    # lower is refused (a row whose step cost varies: ROADMAP Queue 2).
    n_passes: int = 1
    exit_threshold: float = 1.0
    # Sandwich norms: a second RMSNorm after each sublayer, inside the
    # residual branch (``x + norm(attn(norm(x)))``).
    post_norms: bool = False
    # Latent attention (DeepSeek-V3's MLA, no query compression) where
    # ``kv_lora_rank`` > 0: a head's query is ``qk_nope_head_dim`` +
    # ``qk_rope_head_dim`` wide; its key and value come from ONE normed
    # latent of ``kv_lora_rank`` a token through per-head up-projections
    # (key ``qk_nope_head_dim`` + the rotary key, value ``v_head_dim``),
    # and the rotary key is one for all heads. A cache holds the latent
    # and the rotary key alone (``latent_width``); a decode step reads
    # them with the up-projections absorbed into the query and the output.
    # The rotary part rotates half-split pairs.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # A latent model's experts (DeepSeek-V3's noaux_tc router, one group;
    # every other model with ``n_experts`` has the GShard layer, moe_ffn):
    # sigmoid scores, the top ``expert_top_k`` by score plus a selection
    # bias held beside the router (the bias selects, it never weighs), the
    # chosen scores normed and times ``routed_scaling``; every (token,
    # choice) is computed, none dropped (moe_dropless), beside one MLP of
    # ``n_shared_experts`` x ``d_expert`` that every token takes. The
    # first ``first_k_dense`` layers have the dense ``d_ff`` MLP instead;
    # a routed expert is ``d_expert`` wide.
    routed_scaling: float = 1.0
    n_shared_experts: int = 0
    d_expert: int = 0
    first_k_dense: int = 0

    def __post_init__(self):
        if self.kv_lora_rank:
            self._check_latent()
        if self.n_passes < 1:
            raise ValueError(f"n_passes must be >= 1, got {self.n_passes}")
        if self.exit_threshold < 1.0:
            raise ValueError(
                f"exit_threshold {self.exit_threshold} < 1: exit before "
                "the last pass is not supported (every token runs all "
                f"{self.n_passes} passes; the logits are the last pass's)")
        if (self.n_passes > 1 or self.post_norms) and (
                self.mixer_types or self.n_experts or self.context_parallel
                or self.pipeline_stages):
            raise ValueError(
                "n_passes > 1 / post_norms compose with the stacked dense "
                "block on one chip or a tensor mesh: no mixer_types, MoE, "
                "context_parallel or pipeline_stages")
        if not self.mixer_types:
            return
        if len(self.mixer_types) != self.n_layers:
            raise ValueError(
                f"mixer_types names {len(self.mixer_types)} layers, "
                f"n_layers is {self.n_layers}")
        unknown = sorted(set(self.mixer_types) - set(MIXER_KINDS))
        if unknown:
            raise ValueError(f"unknown mixer kind(s) {unknown}; "
                             f"known: {list(MIXER_KINDS)}")
        if self.n_experts or self.context_parallel or self.pipeline_stages:
            raise ValueError("mixer_types composes with the dense FFN on "
                             "one chip: no MoE, context_parallel or "
                             "pipeline_stages")
        self.sparse_spec  # validates the selection's sizes

    def _check_latent(self):
        """Latent attention and the dropless sigmoid-routed experts come
        as one block (the tree :func:`_init_latent` draws): served on one
        chip, by the plain stacked loop."""
        if not (self.qk_nope_head_dim and self.qk_rope_head_dim
                and self.v_head_dim and self.n_experts and self.d_expert):
            raise ValueError(
                "latent attention (kv_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim, v_head_dim) and sigmoid-routed experts "
                "(n_experts, d_expert) come together: set all of them")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if not 0 <= self.first_k_dense < self.n_layers:
            raise ValueError(f"first_k_dense {self.first_k_dense} must leave "
                             f"an expert layer of {self.n_layers}")
        if (self.mixer_types or self.n_passes > 1 or self.post_norms
                or self.context_parallel or self.pipeline_stages):
            raise ValueError(
                "latent attention composes with the plain stacked block on "
                "one chip: no mixer_types, n_passes, post_norms, "
                "context_parallel or pipeline_stages")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def rope_dim(self) -> int:
        """The width the rotary tables are made for: a latent model's
        rotary part, every other model's whole head."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.head_dim

    @property
    def latent_width(self) -> int:
        """A latent cache's row a token and layer: the latent and the
        rotary key, padded with zeros to whole 128-lane tiles (a TPU
        lays the row out so whatever its width; the padding is never
        read as data)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def cache_layers(self) -> int:
        """The layers of K/V a row holds, the one number every K/V store
        is sized by: one per (pass, layer) of the stack, index
        ``pass * n_layers + layer``; of a ``mixer_types`` model, its
        sparse layers alone."""
        if self.mixer_types:
            return len(self.layers_of(MIXER_SPARSE))
        return self.n_layers * self.n_passes

    @property
    def sparse_spec(self) -> SparseSpec:
        return SparseSpec(
            kernel=self.sparse_kernel_size, stride=self.sparse_kernel_stride,
            block=self.sparse_block_size, topk=self.sparse_topk,
            init_blocks=self.sparse_init_blocks, window=self.sparse_window,
            dense_len=self.sparse_dense_len)

    def layers_of(self, kind: str) -> tuple[int, ...]:
        """Indices of the layers whose mixer is ``kind``."""
        return tuple(i for i, m in enumerate(self.mixer_types) if m == kind)


# Named presets; sizes per the public Llama-3/TinyLlama shapes.
PRESETS: dict[str, TransformerConfig] = {
    "llama3-8b": TransformerConfig(
        vocab_size=128_256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14_336, rope_theta=500_000.0,
    ),
    "llama-1b": TransformerConfig(
        vocab_size=32_000, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=8, d_ff=5632,
    ),
    "lm-test-tiny": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, remat=False,
    ),
    # Single-chip flagship bench config: llama-style blocks at d=4096 with
    # a 5×d FFN and llama-3.2-style GQA (32 query / 4 kv heads), 3 layers /
    # 32k vocab — 1.13B params, the widest matmuls that fit 16GB HBM with
    # adafactor. MXU efficiency rises with contraction width, and at 3
    # layers the activations fit without remat while the unrolled layer
    # loop avoids the scan's saved-activation stacking. An invented
    # shape, chosen for MFU: ROADMAP Queue 2 replaces it in the
    # benchmark; what it measures today is in PERF.md.
    "flagship-1b": TransformerConfig(
        vocab_size=32_000, d_model=4096, n_layers=3, n_heads=32,
        n_kv_heads=4, d_ff=20_480, max_seq_len=2048, remat=False,
        scan_layers=False, attn_impl="splash", attn_block_k=1024,
    ),
    # Realistic-depth flagship: 16 llama-style layers (VERDICT r2 #1 —
    # the depth class of BERT/Llama users actually bring), 1.53B params,
    # the widest 16-layer geometry that keeps ~2GB HBM headroom on a
    # 16GB v5e (configs close to the HBM limit thrash). The deep recipe
    # vs the shallow flagship: unrolled layers + the "llm" named-save
    # remat policy (save gate/up/attn-context, recompute the cheap rest)
    # and bf16 gradients (OptimizerConfig.grad_dtype) — each buys HBM
    # that goes straight into width. The GQA-native splash attention
    # kernel (fused bwd + causal block skipping) replaced the
    # single-block XLA path, and the unchunked LM loss replaced
    # loss_chunks=8 (the splash memory savings make the full logits
    # fit). What it measures today is in PERF.md.
    "flagship-deep": TransformerConfig(
        vocab_size=32_000, d_model=3072, n_layers=16, n_heads=24,
        n_kv_heads=4, d_ff=6656, max_seq_len=2048, remat=True,
        remat_policy="llm", scan_layers=False, loss_chunks=0,
        attn_impl="splash", attn_block_k=1024,
    ),
    # Mixtral-family shape at reduced depth (8 experts, top-2).
    "moe-1b": TransformerConfig(
        vocab_size=32_000, d_model=1024, n_layers=8, n_heads=16,
        n_kv_heads=4, d_ff=3584, n_experts=8, expert_top_k=2,
    ),
    "moe-test-tiny": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, remat=False, n_experts=4,
        expert_top_k=2,
    ),
    # MiniCPM-SALA as published (openbmb/MiniCPM-SALA config.json): 24
    # lightning and 8 sparse layers, the sparse ones where its mixer_types
    # has them; muP scale_emb 12, scale_depth 1.4, dim_model_base 256.
    # Serving only, on the paged layout (docs/serving.md says what refuses
    # it); at bf16 its 9 B parameters want more than one 16 GB chip.
    "minicpm-sala-9b": TransformerConfig(
        vocab_size=73_448, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=2, d_ff=16_384, max_seq_len=524_288, rope_theta=10_000.0,
        norm_eps=1e-6, remat=False,
        mixer_types=tuple(
            MIXER_SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31)
            else MIXER_LIGHTNING for i in range(32)),
        embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5,
        head_scale=256 / 4096,
    ),
    # The same two mixers at toy widths, every selection size shrunk with
    # them (block 8, windows of 4 by 2, 6 blocks a query, local window 16,
    # dense to 32 tokens): what the CPU tests serve.
    "sala-test-tiny": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, rope_theta=10_000.0, norm_eps=1e-6,
        remat=False,
        mixer_types=(MIXER_SPARSE, MIXER_LIGHTNING, MIXER_LIGHTNING,
                     MIXER_LIGHTNING) * 2,
        embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5, head_scale=0.25,
        sparse_kernel_size=4, sparse_kernel_stride=2, sparse_block_size=8,
        sparse_topk=6, sparse_init_blocks=1, sparse_window=16,
        sparse_dense_len=32, lightning_chunk=8,
    ),
    # Ouro-2.6B as published (ByteDance/Ouro-2.6B config.json): 48 layers
    # run total_ut_steps = 4 times a token, sandwich norms, MHA 16 x 128,
    # untied head; early_exit_threshold 1.0, so the logits are the fourth
    # pass's. Serving only; 192 cache layers = 1.5 MiB of K/V a token
    # (docs/serving.md says what refuses it).
    "ouro-2.6b": TransformerConfig(
        vocab_size=49_152, d_model=2048, n_layers=48, n_heads=16,
        n_kv_heads=16, d_ff=5632, max_seq_len=65_536, rope_theta=1e6,
        norm_eps=1e-6, remat=False, n_passes=4, post_norms=True,
    ),
    # The same loop at toy widths: what the CPU tests serve.
    "ouro-test-tiny": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, rope_theta=1e6, norm_eps=1e-6,
        remat=False, n_passes=4, post_norms=True,
    ),
    # Kanana-2-30B-A3B as published (kakaocorp/kanana-2-30b-a3b-instruct-
    # 2601 config.json, model_type deepseek_v3): latent attention over a
    # 512 + 64 cache, no query compression; a dense first layer, then 47
    # layers of 128 sigmoid-routed experts (6 a token, routed_scaling
    # 2.448) beside a shared MLP of 2 x 768. Serving only, on the dense
    # layout; its 30.7 B parameters want 8 chips (docs/serving.md says
    # what refuses it).
    "kanana-2-30b-a3b": TransformerConfig(
        vocab_size=128_256, d_model=2048, n_layers=48, n_heads=32,
        n_kv_heads=32, d_ff=6144, max_seq_len=32_768, rope_theta=1e6,
        norm_eps=1e-6, remat=False, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_experts=128, expert_top_k=6,
        routed_scaling=2.448, n_shared_experts=2, d_expert=768,
        first_k_dense=1,
    ),
    # The same block at toy widths (a dense layer and two expert layers of
    # 8 experts, 2 a token, one shared; latent 32 + rotary 16): what the
    # CPU tests serve.
    "kanana-test-tiny": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, rope_theta=1e6, norm_eps=1e-6,
        remat=False, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, n_experts=8, expert_top_k=2,
        routed_scaling=2.448, n_shared_experts=1, d_expert=32,
        first_k_dense=1,
    ),
}


def config(name: str, **overrides) -> TransformerConfig:
    return replace(PRESETS[name], **overrides)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init(key, cfg: TransformerConfig):
    """Parameter pytree; weights float32. Training keeps them so (masters,
    cast to cfg.dtype inside the step by :func:`cast_param`); serving casts
    once, when a tree is installed (:func:`serving_params`). A
    ``mixer_types`` model (served only; its float32 tree would not leave a
    16 GB chip room) has each matrix at cfg.dtype as it is drawn: the tree
    :func:`serving_params` would give, one leaf of float32 at a time."""
    if cfg.mixer_types:
        return _init_hybrid(key, cfg)
    if cfg.kv_lora_rank:
        return _init_latent(key, cfg)
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.head_dim
    # NOTE: split count must stay 8 — changing it would silently reshuffle
    # every existing model's init for a given seed (threefry pairs counters
    # with the split width). Extra keys come from fold_in, like lm_head.
    keys = jax.random.split(key, 8)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)

    def stack(k, shape, fan_in):
        return dense(k, (cfg.n_layers, *shape), fan_in)

    if cfg.n_experts:
        e = cfg.n_experts
        mlp = {
            "router": stack(jax.random.fold_in(key, 98), (d, e), d),
            "gate": stack(keys[5], (e, d, f), d),
            "up": stack(keys[6], (e, d, f), d),
            "down": stack(keys[7], (e, f, d), f),
        }
    else:
        mlp = {
            "gate": stack(keys[5], (d, f), d),
            "up": stack(keys[6], (d, f), d),
            "down": stack(keys[7], (f, d), f),
        }
    params = {
        "embed": {"kernel": dense(keys[0], (cfg.vocab_size, d), d)},
        "layers": {
            "attn": {
                "wq": stack(keys[1], (d, cfg.n_heads * hd), d),
                "wk": stack(keys[2], (d, cfg.n_kv_heads * hd), d),
                "wv": stack(keys[3], (d, cfg.n_kv_heads * hd), d),
                "wo": stack(keys[4], (cfg.n_heads * hd, d), cfg.n_heads * hd),
            },
            "mlp": mlp,
            "ln_attn": jnp.ones((cfg.n_layers, d), jnp.float32),
            "ln_mlp": jnp.ones((cfg.n_layers, d), jnp.float32),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if cfg.post_norms:
        for name in ("ln_attn_post", "ln_mlp_post"):
            params["layers"][name] = jnp.ones((cfg.n_layers, d), jnp.float32)
    if cfg.n_passes > 1:
        # The exit gate is the model's, so it is in the tree; at
        # exit_threshold 1.0 no forward reads it.
        params["exit_gate"] = {
            "kernel": dense(jax.random.fold_in(key, 97), (d, 1), d),
            "bias": jnp.zeros((1,), jnp.float32)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": dense(jax.random.fold_in(key, 99), (d, cfg.vocab_size), d)
        }
    return params


def _init_hybrid(key, cfg: TransformerConfig):
    """The tree of a ``mixer_types`` model: ``layers`` is a list with one
    tree per layer, ``mixer`` shaped by the layer's kind. Both kinds hold
    wq, wo and the gate wg at [d, d], a gain per head dim for q and k;
    lightning k/v are as wide as q and its output has a norm gain, the
    sparse k/v are ``n_kv_heads`` wide."""
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    q_dim = cfg.n_heads * hd

    def dense(k, shape, fan_in=None):
        w = jax.random.normal(k, shape, jnp.float32) * (
            fan_in or shape[0]) ** -0.5
        return w.astype(cfg.dtype)

    layers = []
    for i, kind in enumerate(cfg.mixer_types):
        ks = jax.random.split(jax.random.fold_in(key, 1000 + i), 8)
        kv_dim = q_dim if kind == MIXER_LIGHTNING else cfg.n_kv_heads * hd
        mixer = {
            "wq": dense(ks[0], (d, q_dim)), "wk": dense(ks[1], (d, kv_dim)),
            "wv": dense(ks[2], (d, kv_dim)), "wo": dense(ks[3], (q_dim, d)),
            "wg": dense(ks[4], (d, q_dim)),
            "q_norm": jnp.ones((hd,), jnp.float32),
            "k_norm": jnp.ones((hd,), jnp.float32),
        }
        if kind == MIXER_LIGHTNING:
            mixer["o_norm"] = jnp.ones((q_dim,), jnp.float32)
        layers.append({
            "mixer": mixer,
            "mlp": {"gate": dense(ks[5], (d, f)), "up": dense(ks[6], (d, f)),
                    "down": dense(ks[7], (f, d))},
            "ln_attn": jnp.ones((d,), jnp.float32),
            "ln_mlp": jnp.ones((d,), jnp.float32),
        })
    keys = jax.random.split(key, 2)
    params = {
        "embed": {"kernel": dense(keys[0], (cfg.vocab_size, d), d)},
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense(keys[1], (d, cfg.vocab_size))}
    return params


def _init_latent(key, cfg: TransformerConfig):
    """The tree of a latent-attention model: ``dense_layers`` (the first
    ``first_k_dense``, a dense MLP) and ``layers`` (the expert layers),
    each a stacked tree. A layer's ``attn``: ``wq`` [D, H*(nope+rope)],
    ``wkv_a`` [D, rank+rope] (the latent and the rotary key), ``kv_norm``
    [rank], the up-projections ``w_uk`` [H, nope, rank] and ``w_uv``
    [H, rank, v] (a head's key and value from the latent), ``wo``
    [H*v, D]. An expert layer's ``mlp``: ``router`` [D, E] and ``bias``
    [E] float32, the experts' ``gate``/``up`` [E, D, d_expert] and
    ``down`` [E, d_expert, D], the shared ``shared_gate``/``shared_up``
    [D, n_shared*d_expert] and ``shared_down``. Matrices are drawn at
    cfg.dtype a layer's leaf at a time (a float32 tree of this size would
    not leave a 16 GB chip room, as with ``mixer_types``)."""
    d, h, rank = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    e, fe = cfg.n_experts, cfg.d_expert
    fs = cfg.n_shared_experts * fe
    attn_shapes = {"wq": ((d, h * (nope + rope)), d),
                   "wkv_a": ((d, rank + rope), d),
                   "w_uk": ((h, nope, rank), nope),
                   "w_uv": ((h, rank, v), rank),
                   "wo": ((h * v, d), h * v)}
    dense_mlp = {"gate": ((d, cfg.d_ff), d), "up": ((d, cfg.d_ff), d),
                 "down": ((cfg.d_ff, d), cfg.d_ff)}
    expert_mlp = {"gate": ((e, d, fe), d), "up": ((e, d, fe), d),
                  "down": ((e, fe, d), fe), "shared_gate": ((d, fs), d),
                  "shared_up": ((d, fs), d), "shared_down": ((fs, d), fs)}

    def matrix(layers, i, shape_fan):
        shape, fan_in = shape_fan
        return jnp.stack([
            (jax.random.normal(jax.random.fold_in(
                jax.random.fold_in(key, 1000 + layer), i), shape,
                jnp.float32) * fan_in ** -0.5).astype(cfg.dtype)
            for layer in layers])

    def stack(layers, mlp_shapes):
        n = len(layers)
        mlp = {name: matrix(layers, 10 + i, shape)
               for i, (name, shape) in enumerate(mlp_shapes.items())}
        if mlp_shapes is expert_mlp:
            mlp["router"] = jnp.stack([
                jax.random.normal(jax.random.fold_in(jax.random.fold_in(
                    key, 1000 + layer), 9), (d, e), jnp.float32) * d ** -0.5
                for layer in layers])
            mlp["bias"] = jnp.zeros((n, e), jnp.float32)
        attn = {name: matrix(layers, i, shape)
                for i, (name, shape) in enumerate(attn_shapes.items())}
        attn["kv_norm"] = jnp.ones((n, rank), jnp.float32)
        return {"attn": attn, "mlp": mlp,
                "ln_attn": jnp.ones((n, d), jnp.float32),
                "ln_mlp": jnp.ones((n, d), jnp.float32)}

    keys = jax.random.split(key, 2)
    k = cfg.first_k_dense
    params = {
        "embed": {"kernel": (jax.random.normal(
            keys[0], (cfg.vocab_size, d), jnp.float32) * d ** -0.5
        ).astype(cfg.dtype)},
        "dense_layers": stack(range(k), dense_mlp),
        "layers": stack(range(k, cfg.n_layers), expert_mlp),
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": (jax.random.normal(
            keys[1], (d, cfg.vocab_size), jnp.float32) * d ** -0.5
        ).astype(cfg.dtype)}
    return params


def partition_rules(cfg: TransformerConfig) -> list[PartitionRule]:
    """DP×FSDP×TP(×EP) layout. Stacked layer weights carry a leading L dim
    (never sharded). Megatron pairing: column-parallel in (wq/wk/wv/gate/up),
    row-parallel out (wo/down) so each block needs one reduce per residual
    add. MoE expert weights [L, E, ...] shard E over the expert axis."""
    # Stacked layer weights' leading L dim maps onto pipeline stages when
    # pipeline parallelism is on (each stage holds its contiguous slice).
    ldim = AXIS_PIPELINE if cfg.pipeline_stages > 1 else None
    rules = [
        PartitionRule(r"embed/kernel", P(AXIS_TENSOR, AXIS_FSDP)),
        # A serving tree's stacked q/k/v, held transposed [L, N, D]
        # (serving_params): column-parallel on their middle axis. Ahead of
        # the rule below, whose search would match these paths too.
        PartitionRule(r"attn/w[qkv]_t$", P(ldim, AXIS_TENSOR, AXIS_FSDP)),
        PartitionRule(r"attn/w[qkv]", P(ldim, AXIS_FSDP, AXIS_TENSOR)),
        PartitionRule(r"attn/wo", P(ldim, AXIS_TENSOR, AXIS_FSDP)),
    ]
    if cfg.pipeline_stages > 1:
        rules.append(PartitionRule(r"layers/ln_", P(AXIS_PIPELINE)))
    if cfg.n_experts:
        rules += [
            PartitionRule(r"mlp/router", P(ldim, AXIS_FSDP, None)),
            PartitionRule(
                r"mlp/(gate|up)",
                P(ldim, AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR),
            ),
            PartitionRule(
                r"mlp/down", P(ldim, AXIS_EXPERT, AXIS_TENSOR, AXIS_FSDP)
            ),
        ]
    else:
        rules += [
            PartitionRule(r"mlp/(gate|up)", P(ldim, AXIS_FSDP, AXIS_TENSOR)),
            PartitionRule(r"mlp/down", P(ldim, AXIS_TENSOR, AXIS_FSDP)),
        ]
    rules.append(PartitionRule(r"lm_head/kernel", P(AXIS_FSDP, AXIS_TENSOR)))
    # norms replicated (fall through to default P()).
    return rules


def batch_partition_spec(cfg: TransformerConfig) -> P:
    if cfg.context_parallel:
        return P((AXIS_DATA, AXIS_FSDP), AXIS_SEQUENCE)
    return P((AXIS_DATA, AXIS_FSDP), None)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def cast_param(w, dtype):
    """A parameter leaf at the compute dtype. Every such cast goes through
    here (this module and models/decode.py), so the convert carries the
    ``cast_weights`` scope wherever XLA hoists or fuses it."""
    with scope(SCOPE_CAST_WEIGHTS):
        return w.astype(dtype)


def project(x, attn, name: str, dtype):
    """``x`` [..., D] through the attention's ``w<name>`` (q, k or v). A
    serving tree holds the stacked matrix transposed as ``w<name>_t``,
    [N, D] a layer (:func:`serving_params`), and the dot contracts the
    minor axes of both, so a layer's slice is the operand as stored; any
    other tree (training's, a mixer layer's) holds ``w<name>`` [D, N]."""
    held = attn.get(f"w{name}_t")
    if held is None:
        return x @ cast_param(attn[f"w{name}"], dtype)
    # The product is a value of its own, [..., N]: XLA then reads a layer
    # of the stack inside the dot's fusion, as it does for wo and the
    # MLP. With the caller's split into heads fused into the dot, XLA:TPU
    # slices the layer out first, an op of its own (a layer application's
    # three, outside the `attn` scope; described v5e compile, PR 39).
    return lax.optimization_barrier(lax.dot_general(
        x, cast_param(held, dtype), (((x.ndim - 1,), (1,)), ((), ()))))


# The leaves cast_param is applied to at cfg.dtype. Norm gains are used
# in float32 by rms_norm and the MoE router is cast to float32: not here.
_COMPUTE_DTYPE_LEAF = re.compile(
    r"^(embed/kernel|lm_head/kernel|layers/attn/w[qkvo]"
    r"|layers/mlp/(gate|up|down)"
    r"|layers/\d+/mixer/w[qkvog]|layers/\d+/mlp/(gate|up|down)"
    r"|(dense_)?layers/attn/(wq|wkv_a|w_uk|w_uv|wo)"
    r"|(dense_)?layers/mlp/(shared_)?(gate|up|down))$")
# The stacked q/k/v that a layer loop slices inside its scan. Held as
# [L, D, N], XLA relays each to [L, N, D] ({1,2,0}) for the dot, a whole
# stack every step; a serving tree holds them so (PERF.md, PR 39).
_STACKED_QKV = re.compile(r"^layers/attn/w[qkv]$")


def serving_params(params, cfg: TransformerConfig, *, transpose_qkv=True):
    """``params`` as a serving replica holds them: every leaf the forward
    casts to ``cfg.dtype`` is at ``cfg.dtype`` already, so no dispatch
    casts a weight again (cast_param on such a leaf emits no op). Same
    rounding as inside the step, so tokens and logits are bit for bit
    what the float32 tree gives. The stacked q/k/v are held transposed,
    ``w?_t`` [L, N, D], read by :func:`project`; ``transpose_qkv=False``
    keeps the checkpoint's names and layout (the engine's tree until a
    decoder takes it: what a caller reads it by). A serving tree comes
    back as the same leaves. Leaf by leaf, each original let go as soon
    as its copy exists: a caller that hands over its only reference (the
    argument built in the call) pays one leaf of transient, not a second
    tree."""
    dtype = jnp.dtype(cfg.dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    del params
    paths = [path_str(kp) for kp, _ in flat]
    leaves = [leaf for _, leaf in flat]
    del flat
    moved = []
    for i, path in enumerate(paths):
        if _COMPUTE_DTYPE_LEAF.match(path) and leaves[i].dtype != dtype:
            leaves[i] = jax.block_until_ready(cast_param(leaves[i], dtype))
        if transpose_qkv and _STACKED_QKV.match(path):
            leaves[i] = jax.block_until_ready(leaves[i].swapaxes(1, 2))
            moved.append(path.rsplit("/", 1)[1])
    params = jax.tree_util.tree_unflatten(treedef, leaves)
    for name in moved:
        attn = params["layers"]["attn"]
        attn[f"{name}_t"] = attn.pop(name)
    return params


def _constrain(x, mesh, spec):
    if mesh is not None:
        x = lax.with_sharding_constraint(x, jax.NamedSharding(mesh, spec))
    return x


@scope(SCOPE_ATTN)
def _attention(x, layer, cfg: TransformerConfig, rope, mesh):
    b, t, d = x.shape
    hd = cfg.head_dim
    cos, sin = rope
    q = project(x, layer, "q", cfg.dtype).reshape(b, t, cfg.n_heads, hd)
    k = project(x, layer, "k", cfg.dtype).reshape(b, t, cfg.n_kv_heads, hd)
    v = project(x, layer, "v", cfg.dtype).reshape(b, t, cfg.n_kv_heads, hd)
    # Inert unless the policy names them ("llm_qkv"): saving post-rope
    # q/k/v spares the backward from re-running rms_norm + the three
    # projections + rope just to rebuild the flash kernel's residuals.
    q = checkpoint_name(apply_rotary(q, cos, sin), "attn_q")
    k = checkpoint_name(apply_rotary(k, cos, sin), "attn_k")
    v = checkpoint_name(v, "attn_v")
    if cfg.context_parallel:
        # Ring over the sequence axis; GQA folded by repeating KV heads
        # (ring kernel is MHA). [B,T,H,D] -> [B,H,T,D].
        reps = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
        out = ring_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            mesh,
            causal=True,
        ).transpose(0, 2, 1, 3)
    else:
        out = flash_attention(
            q, k, v, causal=True,
            implementation=cfg.attn_impl,
            block_k=cfg.attn_block_k,
            mesh=mesh,
        )
    out = out.reshape(b, t, cfg.n_heads * hd)
    # Inert without the "llm" policy: wo's backward reuses its input, so
    # saving it here spares recomputing the whole attention block.
    out = checkpoint_name(out, "attn_ctx")
    return out @ cast_param(layer["wo"], cfg.dtype)


@scope(SCOPE_MLP)
def _mlp(x, layer, cfg: TransformerConfig):
    gate = checkpoint_name(x @ cast_param(layer["gate"], cfg.dtype),
                           "mlp_gate")
    up = checkpoint_name(x @ cast_param(layer["up"], cfg.dtype), "mlp_up")
    return (jax.nn.silu(gate) * up) @ cast_param(layer["down"], cfg.dtype)


@scope(SCOPE_MLP)
def moe_ffn(x, mlp, cfg: TransformerConfig, token_valid=None):
    """GShard-style MoE FFN: top-k routing with static per-expert capacity.

    Everything is fixed-shape einsums (no gather/scatter, no dynamic
    shapes): tokens are dispatched into [E, C, D] expert buffers via a
    one-hot dispatch tensor, each expert runs a batched SwiGLU (weights
    stacked on a leading E dim, sharded over the `expert` mesh axis —
    GSPMD turns the dispatch/combine einsums into all-to-alls over ICI),
    and outputs combine back weighted by the normalized gate. Tokens past
    an expert's capacity are dropped and ride the residual connection.

    x: [B, T, D] → (y [B, T, D], aux_loss scalar) — aux is the
    load-balancing loss (Switch/GShard: E · Σ_e fraction_e · mean_prob_e).

    ``token_valid`` ([B, T] bool): padding tokens claim no expert capacity
    and are excluded from the aux statistics — without this, a ragged
    serving batch's pad slots would evict real tokens from their experts.
    """
    b, t, d = x.shape
    e = cfg.n_experts
    k = min(cfg.expert_top_k, e)
    n = b * t
    capacity = max(int(n * k / e * cfg.expert_capacity_factor), k)
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32)
              @ cast_param(mlp["router"], jnp.float32))  # router in fp32
    probs = jax.nn.softmax(logits, axis=-1)  # [n, e]
    gate_vals, expert_idx = lax.top_k(probs, k)  # [n, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    oh_e = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # [n, k, e]
    n_valid = jnp.float32(n)
    if token_valid is not None:
        tv = token_valid.reshape(n).astype(jnp.float32)
        oh_e = oh_e * tv[:, None, None]
        n_valid = jnp.maximum(jnp.sum(tv), 1.0)
    # Position of each (token, slot) within its expert, priority-major:
    # all first choices are placed before any second choice (GShard order).
    flat = oh_e.transpose(1, 0, 2).reshape(k * n, e)
    pos = (jnp.cumsum(flat, axis=0) - flat).reshape(k, n, e).transpose(
        1, 0, 2
    )
    slot_pos = jnp.sum(pos * oh_e, axis=-1)  # [n, k]
    keep = slot_pos < capacity
    oh_c = jax.nn.one_hot(
        jnp.where(keep, slot_pos, 0), capacity, dtype=jnp.float32
    ) * keep[..., None]  # [n, k, c]

    dispatch = jnp.einsum("nke,nkc->nec", oh_e, oh_c)
    combine = jnp.einsum(
        "nke,nkc,nk->nec", oh_e, oh_c, gate_vals
    ).astype(cfg.dtype)

    expert_in = jnp.einsum(
        "nd,nec->ecd", xf, dispatch.astype(cfg.dtype)
    )  # [e, c, d]
    g = jnp.einsum("ecd,edf->ecf", expert_in,
                   cast_param(mlp["gate"], cfg.dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in,
                   cast_param(mlp["up"], cfg.dtype))
    out = jnp.einsum(
        "ecf,efd->ecd", jax.nn.silu(g) * u,
        cast_param(mlp["down"], cfg.dtype)
    )
    y = jnp.einsum("ecd,nec->nd", out, combine)

    # Load-balance aux: fraction of top-1 tokens per expert × mean router
    # prob per expert (differentiable through probs only; valid tokens only).
    top1_frac = jnp.sum(oh_e[:, 0, :], axis=0) / n_valid
    if token_valid is not None:
        mean_prob = jnp.sum(
            probs * token_valid.reshape(n, 1), axis=0
        ) / n_valid
    else:
        mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(top1_frac * mean_prob)
    return y.reshape(b, t, d).astype(x.dtype), aux


def _grouped(xs, w, sizes, layer):
    """``lax.ragged_dot`` of rows ``xs`` grouped by ``sizes`` [E] against
    experts ``w`` [E, ...]; with ``layer`` (traced), ``w`` is the WHOLE
    stack [L, E, ...] of every expert layer, read as L x E groups of which
    only the layer's hold rows: a slice of the stack would be a copy of
    the layer's experts (the product is a kernel that takes its operand
    whole), as large as what the product reads."""
    if layer is None:
        return lax.ragged_dot(xs, w, sizes)
    n_layers, e = w.shape[:2]
    every = lax.dynamic_update_slice(
        jnp.zeros((n_layers * e,), sizes.dtype), sizes, (layer * e,))
    return lax.ragged_dot(xs, w.reshape(n_layers * e, *w.shape[2:]), every)


def moe_dropless(x, mlp, cfg: TransformerConfig, token_valid=None,
                 layer=None):
    """The expert layer of a latent model (``kv_lora_rank``) on x [..., D]:
    ``(y [..., D], routed [E] int32)``, ``routed`` the tokens each expert
    took (of those ``token_valid`` marks: a pad token is routed and
    computed like any, counted by none; with no capacity it displaces
    nobody).

    Router in float32: s = sigmoid(x W_r); the top ``expert_top_k`` by
    s + bias; weights ``routed_scaling`` x s over their sum (the bias
    selects, it never weighs). Every (token, choice) pair is computed:
    the pairs are sorted by expert and each expert's group goes through
    its matrices as one grouped product (``lax.ragged_dot``: a touched
    expert's weights are read once a call, however many tokens it took),
    then each token's pairs are gathered back and weighed. The shared
    MLP takes every token. With ``layer`` the experts' matrices in
    ``mlp`` are the stacks of every expert layer (:func:`_grouped`).

    Where ``ops/grouped_experts.py:grouped_experts_implementation`` says
    so (a TPU, bf16 experts, at most a row tile of pairs an expert: a
    decode step, not an admission) the three products, the SiLU and the
    gate are ONE kernel (``grouped_expert_mlp``) that reads each touched
    expert once out of the stacks where they lie. A latent model is
    served under no mesh (``serving/continuous.py:_check_latent``)."""
    shape, d = x.shape, x.shape[-1]
    e, k = cfg.n_experts, cfg.expert_top_k
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    dtype = cfg.dtype
    with scope(SCOPE_MOE_ROUTE):
        scores = jax.nn.sigmoid(lax.dot(
            xf.astype(jnp.float32), cast_param(mlp["router"], jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(scores + mlp["bias"], k)  # [n, k]
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = cfg.routed_scaling * picked / jnp.sum(picked, -1,
                                                       keepdims=True)
        flat = chosen.reshape(n * k)
        order = jnp.argsort(flat)
        sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
        valid = (jnp.ones((n,), jnp.int32) if token_valid is None
                 else token_valid.reshape(n).astype(jnp.int32))
        routed = jnp.bincount(flat, weights=jnp.repeat(valid, k),
                              length=e).astype(jnp.int32)
    with scope(SCOPE_MOE_EXPERTS):
        xs = xf.astype(dtype)[order // k]  # [n*k, D], grouped by expert
        if grouped_experts_implementation(n * k, e, d, cfg.d_expert,
                                          dtype) == "pallas":
            stacks = [cast_param(mlp[name], dtype)
                      for name in ("gate", "up", "down")]
            if layer is None:
                stacks = [w[None] for w in stacks]
            out = grouped_expert_mlp(xs, sizes, 0 if layer is None else layer,
                                     *stacks)
        else:
            gate = _grouped(xs, cast_param(mlp["gate"], dtype), sizes, layer)
            up = _grouped(xs, cast_param(mlp["up"], dtype), sizes, layer)
            out = _grouped((jax.nn.silu(gate) * up).astype(dtype),
                           cast_param(mlp["down"], dtype), sizes, layer)
        pairs = out[jnp.argsort(order)].reshape(n, k, d)
        y = jnp.einsum("nkd,nk->nd", pairs.astype(jnp.float32), weight)
        sg = xf @ cast_param(mlp["shared_gate"], dtype)
        su = xf @ cast_param(mlp["shared_up"], dtype)
        y = y + (jax.nn.silu(sg) * su) @ cast_param(mlp["shared_down"], dtype)
    return y.reshape(shape).astype(x.dtype), routed


# ---------------------------------------------------------------------------
# Latent attention (cfg.kv_lora_rank)
# ---------------------------------------------------------------------------
#
# The pieces models/decode.py calls against its latent store, and the
# cache-free forward. A cache row is [c | k_rope | 0], ``latent_width``
# wide: the normed latent, the rotary key, zeros to the tile.


def rotate_half(x, cos, sin):
    """Half-split rotary on x [B, S, H, r] at (cos, sin) [B, S, r/2]
    gathered per row at each token's position: the pairs (x[..., i],
    x[..., i + r/2]). The decode paths' rotary, every model's."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x1 * s + x2 * c], axis=-1
    ).astype(x.dtype)


def mla_inputs(x, attn, cfg: TransformerConfig, rope_bt):
    """x [B, S, D] with rotary tables ``rope_bt`` (cos, sin) [B, S, r/2]
    at each token's position → (q_nope [B, S, H, nope], q_rope
    [B, S, H, rope] rotated, the cache row [B, S, latent_width])."""
    b, s, _ = x.shape
    h, nope, rank = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    rope, dtype = cfg.qk_rope_head_dim, cfg.dtype
    cos, sin = rope_bt
    q = project(x, attn, "q", dtype).reshape(b, s, h, nope + rope)
    kv = x @ cast_param(attn["wkv_a"], dtype)
    c = rms_norm(kv[..., :rank], attn["kv_norm"], eps=cfg.norm_eps)
    k_rope = rotate_half(kv[..., None, rank:], cos, sin)[:, :, 0]
    row = jnp.concatenate(
        [c.astype(dtype), k_rope.astype(dtype),
         jnp.zeros((b, s, cfg.latent_width - rank - rope), dtype)], axis=-1)
    return q[..., :nope], rotate_half(q[..., nope:], cos, sin), row


def mla_expanded(q_nope, q_rope, rows, mask, attn, cfg: TransformerConfig):
    """Latent attention as published: every head's key [k_nope | k_rope]
    and value expanded from ``rows`` [B, T, latent_width]; queries q_nope
    [B, S, H, nope], q_rope [B, S, H, rope]; ``mask`` [B, S, T]. Returns
    [B, S, D] (after ``wo``)."""
    b, s, h, _ = q_nope.shape
    rank, rope, dtype = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.dtype
    c, k_rope = rows[..., :rank], rows[..., rank:rank + rope]
    k_nope = jnp.einsum("btr,hnr->bthn", c, cast_param(attn["w_uk"], dtype))
    v = jnp.einsum("btr,hrv->bthv", c, cast_param(attn["w_uv"], dtype))
    scores = (jnp.einsum("bshn,bthn->bhst", q_nope.astype(jnp.float32),
                         k_nope.astype(jnp.float32))
              + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))
              ) * (cfg.qk_nope_head_dim + rope) ** -0.5
    scores = jnp.where(mask[:, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(dtype)
    o = jnp.einsum("bhst,bthv->bshv", p, v).reshape(b, s, h * cfg.v_head_dim)
    return o.astype(dtype) @ cast_param(attn["wo"], dtype)


def latent_ffn(h, mlp, cfg: TransformerConfig, token_valid=None,
               layer=None):
    """A latent model's layer MLP: the expert layer where the tree has a
    router, the dense SwiGLU of a leading layer; ``(y, routed or
    None)``. ``layer``: as :func:`moe_dropless` takes it."""
    if "router" in mlp:
        with scope(SCOPE_MLP):
            return moe_dropless(h, mlp, cfg, token_valid, layer)
    return _mlp(h, mlp, cfg), None


def _latent_layers(params, x, cfg: TransformerConfig):
    """The cache-free forward of a latent model on x [B, T, D], whole
    sequences from position 0: the leading dense layers, then a scan over
    the expert layers."""
    b, t, _ = x.shape
    cos, sin = rotary_frequencies(cfg.qk_rope_head_dim, t,
                                  theta=cfg.rope_theta)
    rope_bt = (jnp.broadcast_to(cos[None], (b, *cos.shape)),
               jnp.broadcast_to(sin[None], (b, *sin.shape)))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool))[None],
                            (b, t, t))

    def layer_fn(x, layer):
        h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
        with scope(SCOPE_ATTN):
            q_nope, q_rope, rows = mla_inputs(h, layer["attn"], cfg, rope_bt)
            x = x + mla_expanded(q_nope, q_rope, rows, mask, layer["attn"],
                                 cfg)
        h = rms_norm(x, layer["ln_mlp"], eps=cfg.norm_eps)
        return x + latent_ffn(h, layer["mlp"], cfg)[0], None

    for i in range(cfg.first_k_dense):
        x, _ = layer_fn(x, jax.tree.map(lambda w: w[i],
                                        params["dense_layers"]))
    return lax.scan(layer_fn, x, params["layers"])[0]


# ---------------------------------------------------------------------------
# Hybrid mixers (cfg.mixer_types)
# ---------------------------------------------------------------------------
#
# Both kinds: q and k normed per head (RMSNorm over head_dim with a gain),
# the output times sigmoid(x @ wg), then wo. models/decode.py calls the
# same pieces against its caches; :func:`_hybrid_layers` below is the
# cache-free forward.


def mixer_qkv(x, mixer, cfg: TransformerConfig, n_kv: int):
    """x [B, S, D] → q [B, S, H, hd], k, v [B, S, n_kv, hd], q and k
    normed per head, no rotary yet."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = (x @ cast_param(mixer["wq"], cfg.dtype)).reshape(b, s, cfg.n_heads, hd)
    k = (x @ cast_param(mixer["wk"], cfg.dtype)).reshape(b, s, n_kv, hd)
    v = (x @ cast_param(mixer["wv"], cfg.dtype)).reshape(b, s, n_kv, hd)
    q = rms_norm(q, mixer["q_norm"], eps=cfg.norm_eps)
    k = rms_norm(k, mixer["k_norm"], eps=cfg.norm_eps)
    return q, k, v


def mixer_out(o, x, mixer, cfg: TransformerConfig):
    """Heads' output o [B, S, H*hd] → the mixer's: the lightning kind's
    norm over the concatenated heads, the gate, wo."""
    if "o_norm" in mixer:
        o = rms_norm(o, mixer["o_norm"], eps=cfg.norm_eps)
    gate = jax.nn.sigmoid(x @ cast_param(mixer["wg"], cfg.dtype))
    return (o.astype(cfg.dtype) * gate) @ cast_param(mixer["wo"], cfg.dtype)


def lightning_qkv(x, mixer, cfg: TransformerConfig, rope, positions):
    """q, k (rotary at ``positions`` [B, S] over the whole head) and v of a
    lightning layer, each [B, S, H, hd]."""
    q, k, v = mixer_qkv(x, mixer, cfg, cfg.n_heads)
    cos, sin = rope
    return (apply_rotary(q, cos, sin, positions=positions),
            apply_rotary(k, cos, sin, positions=positions), v)


@scope(SCOPE_ATTN)
def lightning_span(x, mixer, cfg: TransformerConfig, rope, positions, state,
                   n_valid):
    """A lightning layer over a span: x [B, S, D] at ``positions``, the
    rows' state [B, H, hd, hd] as the span begins, ``n_valid`` [B] real
    tokens. Returns (out [B, S, D], state after them)."""
    b, s, _ = x.shape
    q, k, v = lightning_qkv(x, mixer, cfg, rope, positions)
    with scope(SCOPE_LINEAR_ATTN):
        o, state = lightning_chunked(q, k, v, state,
                                     lightning_slopes(cfg.n_heads), n_valid,
                                     chunk=cfg.lightning_chunk)
    return mixer_out(o.reshape(b, s, -1), x, mixer, cfg), state


@scope(SCOPE_ATTN)
def lightning_token(x, mixer, cfg: TransformerConfig, rope, positions, state,
                    live):
    """The same layer one token a row: x [B, 1, D]; rows that are not
    ``live`` [B] keep their state."""
    b = x.shape[0]
    q, k, v = lightning_qkv(x, mixer, cfg, rope, positions)
    with scope(SCOPE_LINEAR_ATTN):
        o, new = lightning_step(q[:, 0], k[:, 0], v[:, 0], state,
                                lightning_slopes(cfg.n_heads))
        state = jnp.where(live[:, None, None, None], new, state)
    return mixer_out(o.reshape(b, 1, -1), x, mixer, cfg), state


def sparse_span(q, positions, k_row, v_row, ckeys, x, mixer,
                cfg: TransformerConfig):
    """The sparse layer's read for a span of queries q [B, S, H, hd] over
    a row's keys and values [B, Hkv, T, hd] and its compressed keys."""
    b, s, _, _ = q.shape
    with scope(SCOPE_SPARSE_ATTN):
        o = attend_span(q, positions, k_row, v_row, ckeys, cfg.sparse_spec)
    return mixer_out(o.reshape(b, s, -1), x, mixer, cfg)


@scope(SCOPE_ATTN)
def _sparse_uncached(x, mixer, cfg: TransformerConfig):
    """A sparse layer over whole sequences with no cache: the row is the
    sequence's own keys, padded to whole blocks."""
    b, s, _ = x.shape
    spec = cfg.sparse_spec
    q, k, v = mixer_qkv(x, mixer, cfg, cfg.n_kv_heads)
    pad = -s % spec.block
    k_row, v_row = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))
                            ).transpose(0, 2, 1, 3) for a in (k, v))
    with scope(SCOPE_SPARSE_SELECT):
        ckeys = compress_keys(k_row, spec)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return sparse_span(q, positions, k_row, v_row, ckeys, x, mixer, cfg)


def _hybrid_layers(params, x, cfg: TransformerConfig):
    """The unrolled layer loop of a ``mixer_types`` model on x [B, T, D],
    whole sequences from position 0."""
    b, t, _ = x.shape
    rope = rotary_frequencies(cfg.head_dim, t, theta=cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    n_valid = jnp.full((b,), t, jnp.int32)
    for layer, kind in zip(params["layers"], cfg.mixer_types):
        h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
        if kind == MIXER_LIGHTNING:
            state = jnp.zeros((b, cfg.n_heads, cfg.head_dim, cfg.head_dim),
                              jnp.float32)
            mixed, _ = lightning_span(h, layer["mixer"], cfg, rope, positions,
                                      state, n_valid)
        else:
            mixed = _sparse_uncached(h, layer["mixer"], cfg)
        x = x + cfg.residual_scale * mixed
        h = rms_norm(x, layer["ln_mlp"], eps=cfg.norm_eps)
        x = x + cfg.residual_scale * _mlp(h, layer["mlp"], cfg)
    return x


def final_hidden(x, params, cfg: TransformerConfig):
    """The final norm, and a ``mixer_types`` model's scale before the
    head."""
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    return x * cfg.head_scale if cfg.head_scale != 1.0 else x


def post_norm(y, layer, name: str, cfg: TransformerConfig):
    """A sublayer's output ``y`` through its after-norm ``layer[name]``
    where the block has them (``cfg.post_norms``); ``y`` itself where it
    has none, so a plain block's trace holds nothing of this."""
    if not cfg.post_norms:
        return y
    with scope(SCOPE_POST_NORM):
        return rms_norm(y, layer[name], eps=cfg.norm_eps)


def close_pass(x, params, cfg: TransformerConfig):
    """The final norm, which closes a pass of the stack: the one before
    the head, and in a looped stack also the input of the next pass
    (there it carries the ``loop_norm`` scope)."""
    if cfg.n_passes == 1:
        return rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    with scope(SCOPE_LOOP_NORM):
        return rms_norm(x, params["final_norm"], eps=cfg.norm_eps)


def _layer_fn(cfg: TransformerConfig, mesh, rope, carry, layer):
    x, aux = carry
    act_spec = batch_partition_spec(cfg) + (None,)
    h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
    x = x + post_norm(_attention(h, layer["attn"], cfg, rope, mesh), layer,
                      "ln_attn_post", cfg)
    x = _constrain(x, mesh, P(*act_spec))
    h = rms_norm(x, layer["ln_mlp"], eps=cfg.norm_eps)
    if cfg.n_experts:
        y, layer_aux = moe_ffn(h, layer["mlp"], cfg)
        x = x + y
        aux = aux + layer_aux
    else:
        x = x + post_norm(_mlp(h, layer["mlp"], cfg), layer, "ln_mlp_post",
                          cfg)
    x = _constrain(x, mesh, P(*act_spec))
    return (x, aux), None


def _layer_fn_attn_saved(cfg: TransformerConfig, mesh, rope, mlp_policy,
                         carry, layer):
    """The "llm_attn" remat layout: the attention half runs OUTSIDE any
    checkpoint region — its backward consumes the kernel's own residuals
    (q/k/v/out/logsumexp) instead of re-running rms_norm + the three
    projections + rope + the flash forward — while the FFN half (the bulk
    of saved-activation memory) stays under ``jax.checkpoint`` saving only
    the gate/up projections. At long sequence the attention-rebuild
    recompute is the dominant remat bill; this trades ~120MB/layer of
    residuals for all of it."""
    x, aux = carry
    act_spec = batch_partition_spec(cfg) + (None,)
    h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
    x = x + post_norm(_attention(h, layer["attn"], cfg, rope, mesh), layer,
                      "ln_attn_post", cfg)
    x = _constrain(x, mesh, P(*act_spec))

    @functools.partial(jax.checkpoint, policy=mlp_policy)
    def mlp_part(x, ln, mlp, post):
        h = rms_norm(x, ln, eps=cfg.norm_eps)
        return x + post_norm(_mlp(h, mlp, cfg), post, "ln_mlp_post", cfg)

    x = mlp_part(x, layer["ln_mlp"], layer["mlp"],
                 {k: v for k, v in layer.items() if k == "ln_mlp_post"})
    x = _constrain(x, mesh, P(*act_spec))
    return (x, aux), None


@scope(SCOPE_EMBED)
def _embed_lookup(kernel, tokens, cfg: TransformerConfig, mesh):
    """Token embedding (``kernel`` arrives uncast). Under a tensor-parallel
    mesh the lookup runs as a one-hot matmul: GSPMD partitions matmuls cleanly (contraction over the
    tensor-sharded vocab dim → one reduce), where a gather from a sharded
    table triggers involuntary full rematerialization (spmd_partitioner
    replicate-then-reshard, observed on the dryrun tp path); the backward
    scatter-add becomes a matmul too. Plain gather elsewhere — one-hot costs
    O(B·T·V) flops it only earns back when it buys clean partitioning."""
    kernel = cast_param(kernel, cfg.dtype)
    if mesh is not None and mesh.shape.get(AXIS_TENSOR, 1) > 1:
        one_hot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=kernel.dtype)
        return one_hot @ kernel
    return kernel[tokens]


def hidden_states(params, tokens, cfg: TransformerConfig, *, mesh=None):
    """tokens [B, T] → (final-norm hidden [B, T, D] in cfg.dtype, MoE aux
    loss). The trunk of :func:`apply` without the LM head — the chunked
    training-loss path applies the head inside the loss instead."""
    if cfg.mixer_types:
        x = _embed_lookup(params["embed"]["kernel"], tokens, cfg, mesh)
        x = _hybrid_layers(params, x * cfg.embed_scale, cfg)
        return final_hidden(x, params, cfg), jnp.zeros((), jnp.float32)
    if cfg.kv_lora_rank:
        x = _embed_lookup(params["embed"]["kernel"], tokens, cfg, mesh)
        x = _latent_layers(params, x, cfg)
        return final_hidden(x, params, cfg), jnp.zeros((), jnp.float32)
    t = tokens.shape[1]
    rope = rotary_frequencies(cfg.head_dim, t, theta=cfg.rope_theta)
    x = _embed_lookup(params["embed"]["kernel"], tokens, cfg, mesh)
    x = _constrain(x, mesh, P(*(batch_partition_spec(cfg) + (None,))))

    policy = {
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "dots_batched": jax.checkpoint_policies.dots_saveable,
        "llm": jax.checkpoint_policies.save_only_these_names(
            "attn_ctx", "mlp_gate", "mlp_up"
        ),
        # "llm" + post-rope q/k/v: the flash backward's residual rebuild
        # starts from the saved projections instead of re-running
        # rms_norm/wq/wk/wv/rope. Costs ~(1+2/group)·B·T·D bf16 per layer;
        # buys back the projection recompute — the right trade at long
        # sequence where attention dominates the remat bill.
        "llm_qkv": jax.checkpoint_policies.save_only_these_names(
            "attn_ctx", "mlp_gate", "mlp_up", "attn_q", "attn_k", "attn_v"
        ),
        # Attention outside the remat region entirely (its kernel
        # residuals are saved; only the FFN half is checkpointed) —
        # handled structurally below, not by a save filter.
        "llm_attn": jax.checkpoint_policies.save_only_these_names(
            "mlp_gate", "mlp_up"
        ),
        # "llm" + the splash kernel's own residuals (o/logsumexp, named
        # "attn_res" via residual_checkpoint_name): the backward skips the
        # forward-kernel rerun. Only meaningful with attn_impl="splash".
        "llm_res": jax.checkpoint_policies.save_only_these_names(
            "attn_ctx", "mlp_gate", "mlp_up", "attn_res"
        ),
        "none": None,
    }[cfg.remat_policy]
    attn_saved = cfg.remat and cfg.remat_policy == "llm_attn"

    if cfg.pipeline_stages > 1 and mesh is not None:
        if cfg.n_experts or cfg.context_parallel:
            raise ValueError(
                "pipeline_stages composes with dp/fsdp/tp, not (yet) with "
                "MoE or context parallelism"
            )
        if cfg.n_layers % cfg.pipeline_stages:
            raise ValueError(
                f"n_layers {cfg.n_layers} not divisible by "
                f"pipeline_stages {cfg.pipeline_stages}"
            )
        from kubeflow_tpu.parallel.pipeline import pipeline_apply

        if attn_saved:
            raise ValueError(
                "remat_policy='llm_attn' is incompatible with "
                "pipeline_stages>1 (stages checkpoint whole layers); "
                "use 'llm'"
            )

        def one_layer(layer, h):
            h2 = rms_norm(h, layer["ln_attn"], eps=cfg.norm_eps)
            h = h + _attention(h2, layer["attn"], cfg, rope, None)
            h2 = rms_norm(h, layer["ln_mlp"], eps=cfg.norm_eps)
            return h + _mlp(h2, layer["mlp"], cfg)

        if cfg.remat:
            one_layer = jax.checkpoint(one_layer, policy=policy)
        x = pipeline_apply(one_layer, params["layers"], x, mesh,
                           n_micro=cfg.pipeline_microbatches)
        aux = jnp.zeros((), jnp.float32)
    else:
        if attn_saved:
            if cfg.n_experts:
                raise ValueError(
                    "remat_policy='llm_attn' applies to dense FFN layers; "
                    "MoE models should use 'llm' or 'dots'"
                )
            if cfg.scan_group_size > 1:
                # The grouped scan wraps whole groups in jax.checkpoint,
                # which would discard the attention residuals this policy
                # exists to keep — refuse rather than silently degrade
                # below "llm".
                raise ValueError(
                    "remat_policy='llm_attn' is incompatible with "
                    "scan_group_size>1; use 'llm'"
                )
            layer_fn = functools.partial(
                _layer_fn_attn_saved, cfg, mesh, rope, policy
            )
        else:
            layer_fn = functools.partial(_layer_fn, cfg, mesh, rope)
        carry = (x, jnp.zeros((), jnp.float32))
        if cfg.scan_group_size > 1 and not cfg.scan_layers:
            raise ValueError(
                "scan_group_size applies to the lax.scan representation; "
                "set scan_layers=True (or drop scan_group_size)"
            )
        if cfg.scan_layers and cfg.scan_group_size > 1:
            group = cfg.scan_group_size
            if cfg.n_layers % group:
                raise ValueError(
                    f"n_layers {cfg.n_layers} not divisible by "
                    f"scan_group_size {group}"
                )

            def group_fn(c, layers):
                for i in range(group):
                    layer = jax.tree.map(lambda w: w[i], layers)
                    c, _ = layer_fn(c, layer)
                return c, None

            if cfg.remat:
                group_fn = jax.checkpoint(group_fn, policy=policy)
            grouped = jax.tree.map(
                lambda w: w.reshape(
                    cfg.n_layers // group, group, *w.shape[1:]
                ),
                params["layers"],
            )

            def stack(carry):
                return lax.scan(group_fn, carry, grouped)[0]
        else:
            if cfg.remat and not attn_saved:
                # llm_attn checkpoints inside the layer fn (FFN half only).
                layer_fn = jax.checkpoint(layer_fn, policy=policy)

            def stack(carry):
                if cfg.scan_layers:
                    return lax.scan(layer_fn, carry, params["layers"])[0]
                for i in range(cfg.n_layers):
                    layer = jax.tree.map(lambda w: w[i], params["layers"])
                    carry, _ = layer_fn(carry, layer)
                return carry
        # A looped stack runs the same layers n_passes times, each pass
        # closed by the final norm, whose output the next one takes.
        x, aux = stack(carry)
        for _ in range(1, cfg.n_passes):
            x, aux = stack((close_pass(x, params, cfg), aux))

    return close_pass(x, params, cfg), aux


def head_kernel(params, cfg: TransformerConfig):
    """The LM head matrix [D, V] at the compute dtype."""
    if cfg.tie_embeddings:
        return cast_param(params["embed"]["kernel"], cfg.dtype).T
    return cast_param(params["lm_head"]["kernel"], cfg.dtype)


def apply(params, tokens, cfg: TransformerConfig, *, mesh=None,
          return_aux: bool = False):
    """tokens [B, T] int32 → logits [B, T, V] (cfg.dtype).

    ``return_aux=True`` additionally returns the summed MoE router
    load-balance loss (0.0 for dense models)."""
    x, aux = hidden_states(params, tokens, cfg, mesh=mesh)
    with scope(SCOPE_HEAD):
        logits = x @ head_kernel(params, cfg)
    if return_aux:
        return logits, aux
    return logits


def loss_fn(params, batch, cfg: TransformerConfig, *, mesh=None):
    """Next-token LM loss. batch: {"tokens": [B, T+1] int32} (or separate
    "inputs"/"targets"); negative targets are ignored."""
    from kubeflow_tpu.ops import softmax_cross_entropy
    from kubeflow_tpu.ops.losses import chunked_lm_head_loss

    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    x, aux = hidden_states(params, inputs, cfg, mesh=mesh)
    with scope(SCOPE_HEAD_LOSS):
        head = head_kernel(params, cfg)
        if cfg.loss_chunks:
            b, t, d = x.shape
            loss, metrics = chunked_lm_head_loss(
                x.reshape(b * t, d), head, targets.reshape(b * t),
                z_loss=1e-4, n_chunks=cfg.loss_chunks,
            )
        else:
            loss, metrics = softmax_cross_entropy(x @ head, targets,
                                                  z_loss=1e-4)
    if cfg.n_experts and cfg.router_aux_loss:
        aux_loss = cfg.router_aux_loss * aux
        metrics["router_aux_loss"] = aux_loss
        loss = loss + aux_loss
    return loss, metrics
