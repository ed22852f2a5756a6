"""Continuous-batching decode service with per-token streaming.

TPU-first continuous batching (the capability vLLM/JetStream serve on GPUs,
built the XLA way): a persistent fixed-shape decode state holds up to
``slots`` in-flight sequences, and every decode step is ONE compiled
``[slots, 1]`` forward against the shared KV cache
(:func:`kubeflow_tpu.models.decode.decode_step`). Pending requests are
prefilled at fixed prompt shape — a round's admissions TOGETHER in one
power-of-two-bucketed batch, fused with the state insert into a single
dispatch (``admit_rows``: one round-trip per round, not two per
request) — landing in free rows at step boundaries; a finished row
frees its slot immediately, so a 1-token
request never waits on a 32-token peer — the decoupling VERDICT round 2
asked for over the lockstep batch path (serving/engine.py:_generate_batch).

Two admission-cost levers ride on top (both off by default): a
device-resident **prefix KV cache** (``prefix_cache_slots``) that lets a
prompt whose leading tokens are already pooled gather those K/V rows and
prefill only its suffix (host trie in serving/prefix_cache.py, device
pool + gather/scatter in models/decode.py, publish-on-finish, LRU with
in-flight pins), and **power-of-two prefill length buckets**
(``prefill_len_buckets``) so a short prompt rides a short compiled shape
instead of padding to the full ``prefill_len``.

Decode itself has a throughput lever (off by default): **speculative
decoding** (``speculative_k``). A pluggable proposer
(serving/speculative.py: host n-gram lookup or a small draft model)
guesses up to K tokens per row each round, and ONE fused verify dispatch
(models/decode.py:verify_step) scores them all, keeping each row's
longest accepted prefix plus one committed target token — up to K+1
tokens per dispatch against decode's memory-bandwidth bill of one.
Greedy outputs are byte-identical to speculation off; temperature>0 rows
rejection-resample so their distribution is unchanged. Per-slot draft
length auto-tunes (shrinks while a row's drafts keep missing, recovers
on clean sweeps), and accept/draft counters land in :meth:`metrics`.

Two multi-tenant levers ride the paged pool (both off by default):
a **host-RAM KV tier** (``host_kv_bytes``, serving/kv_tier.py) that
demotes evicted prefix blocks to host memory instead of freeing them
outright — a later trie miss re-imports them through the ordinary
prefix-hit admission, so the effective pool rises past HBM at equal
device bytes — and **QoS admission** (``qos``, serving/qos.py):
per-tenant token buckets at submit, weighted-fair + priority + aging
ordering of the pending queue, deadline shedding, and — under
low-watermark pressure — SUSPENSION of the lowest-priority live stream
(export its KV to the host tier, free its slot and blocks, park the
request) instead of deferring the whole queue; the parked stream
resumes byte-identically through the same prefix-hit admission.

Tokens surface through per-request queues as each step's sample lands —
the REST server streams them as JSON lines over chunked transfer-encoding
and gRPC as a server-streaming method. The reference serves generation
through TF-Serving's opaque batcher (kubeflow/tf-serving/
tf-serving-template.libsonnet:29-49); this is the platform-native engine
with the serving loop exposed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.decode import (
    admit_prefix_and_step,
    admit_rows_and_step,
    copy_block,
    decode_chunk,
    decode_step,
    export_blocks,
    import_blocks,
    init_decode_state,
    init_paged_state,
    init_prefix_pool,
    max_admit_rows,
    paged_admit_prefix_and_step,
    paged_admit_rows_and_step,
    paged_prefill_chunk,
    prefill,
    retire_row,
    shard_decode_state,
    store_blocks,
    store_prefix_cache,
    store_prefix_row,
    verify_chunk,
)
from kubeflow_tpu.models.transformer import serving_params
from kubeflow_tpu.observability.metrics import MetricRegistry
from kubeflow_tpu.observability.tracing import (
    PHASE_COUNTER,
    SCHED_PHASES,
    SLOW_COUNTER,
    SPAN_PREFIX,
    SPAN_ROUND,
    RoundLog,
    RoundRecord,
    TraceStore,
    host_span,
)
from kubeflow_tpu.ops.attention import dense_decode_implementation
from kubeflow_tpu.ops.sparse_attention import decode_implementation
from kubeflow_tpu.serving.affinity import (
    DEFAULT_AFFINITY_TOKENS,
    prefix_affinity_key,
)
from kubeflow_tpu.serving.engine import pow2_bucket
from kubeflow_tpu.serving.kv_allocator import (
    BlockAllocator,
    kv_bytes_per_token,
)
from kubeflow_tpu.serving.kv_directory import COLD_HOLDER
from kubeflow_tpu.serving.kv_tier import HostKvTier, payload_nbytes
from kubeflow_tpu.serving.prefix_cache import PrefixCache
from kubeflow_tpu.serving.qos import (
    DEFAULT_TENANT,
    DeadlineExceeded,
    QosPolicy,
    order_key,
    tenant_bucket,
)
from kubeflow_tpu.serving.speculative import make_proposer

_DONE = object()


class PromptTooLong(ValueError):
    """Terminal admission error: the prompt cannot be served by this
    replica at all — it needs more KV blocks than the whole pool holds,
    or its tokens plus the requested budget exceed the virtual row
    width — so deferring would wait forever. The model server maps this
    to HTTP 413 (vs. the silent-defer path memory PRESSURE takes)."""


@dataclass
class _Request:
    tokens: list[int]
    want: int
    temperature: float
    stream: queue.Queue = field(default_factory=queue.Queue)
    out: list[int] = field(default_factory=list)
    prefill_logits: np.ndarray | None = None
    # Lazy source for prefill_logits: (device array [K, V], row). The
    # vocab-wide logits are ~128KB/row — a device-to-host copy per
    # admission that most requests never read; only the callers that
    # actually read them (want==0 scoring, return_logits) pay it.
    prefill_src: tuple | None = None
    error: Exception | None = None
    # Prefix-cache entry this request's admission read (pinned against
    # eviction until the request finishes).
    pinned_prefix: object | None = None
    # Paged layout: (entry, prefix_len, suffix_bucket) planned at pop
    # time — the plan must precede the block reservation so the entry
    # is pinned before memory-pressure reclaim runs, and so the
    # reservation only covers the NON-shared block count.
    admit_plan: tuple | None = None
    done: threading.Event = field(default_factory=threading.Event)
    submit_t: float = field(default_factory=time.perf_counter)
    ttft_s: float | None = None
    finish_reason: str = "length"
    # Request-scoped trace: id propagated from the gateway (or minted at
    # submit) + the lifecycle timeline recorded into the decoder's
    # TraceStore. last_emit_t feeds the inter-token histogram.
    request_id: str = ""
    timeline: object | None = None
    last_emit_t: float | None = None
    # Scheduler round that first admitted this request (0 = never): the
    # terminal timeline event counts the rounds the stream lived.
    admit_round: int = 0
    # QoS: owning tenant, base priority (tenant default unless the
    # request carried its own), and an absolute shed deadline (None =
    # never shed). ``defer_rounds`` counts rounds this request sat at
    # the head of admission blocked on memory — the HoL-bypass aging
    # counter. ``host_key`` is set while the stream is SUSPENDED: the
    # pinned host-tier entry its resume re-imports.
    tenant: str = DEFAULT_TENANT
    priority: int = 0
    deadline_t: float | None = None
    defer_rounds: int = 0
    host_key: tuple | None = None
    # Emitted tokens already folded into ``tokens`` by an earlier
    # suspension — a later suspension must append only out[folded:],
    # never double-count the first park's fold.
    folded: int = 0
    # Chunked prefill: prompt tokens already scattered into this
    # request's blocks (-1 = not a chunked admission / chain finished).
    # While >= 0 the slot's device row is PARKED (length=total,
    # active=False) and the request must not be suspend-victimized.
    chunk_pos: int = -1
    # True once the first chunk's dispatch stamped the weights epoch,
    # CoW'd the shared tail, and uploaded the table row.
    chunk_started: bool = False
    # Weights epoch this request's PREFILL ran under (stamped inside
    # the admission dispatch's state-lock scope). A finishing stream
    # only publishes its prompt K/V into the prefix trie when this
    # still matches the decoder's live version — a stream that
    # straddled a weight swap computed its prompt K/V under weights
    # the decoder no longer serves.
    weights_version: int = 0

    @property
    def want_left(self) -> int:
        """Tokens still owed. Equals ``want`` for a fresh request; a
        resumed (previously suspended) request already emitted
        ``len(out)`` of its budget, and the device row must only be
        armed for the remainder."""
        return max(self.want - len(self.out), 0)

    def resolve_prefill_logits(self) -> np.ndarray | None:
        if self.prefill_logits is None and self.prefill_src is not None:
            arr, row = self.prefill_src
            self.prefill_logits = np.asarray(arr[row])
            self.prefill_src = None
        return self.prefill_logits


class StreamHandle:
    """Caller-side view of an in-flight generation.

    ``default_timeout`` is the decoder's ``stream_timeout_s`` — callers
    that pass no explicit timeout inherit it, so a deployment expecting
    memory-deferred admissions under load can raise ONE knob instead of
    chasing hard-coded 60s waits through every caller.
    """

    def __init__(self, req: _Request, default_timeout: float = 60.0):
        self._req = req
        self._default_timeout = default_timeout

    def tokens(self, timeout: float | None = None):
        """Yield tokens as the decode loop emits them."""
        if timeout is None:
            timeout = self._default_timeout
        while True:
            try:
                item = self._req.stream.get(timeout=timeout)
            except queue.Empty:
                # queue.Empty's str() is blank — surface a real timeout.
                raise TimeoutError("token stream timed out") from None
            if item is _DONE:
                if self._req.error is not None:
                    raise self._req.error
                return
            yield item

    def result(self, timeout: float | None = None, *,
               with_logits: bool | None = None) -> dict:
        """Block until the request finishes; returns the full prediction.

        ``with_logits``: fetch the vocab-wide prefill logits (a ~128KB
        device transfer). Default None = only when the request emitted
        no tokens (pure-prefill scoring, where the logits ARE the
        answer); pass True to force (return_logits callers).
        """
        if timeout is None:
            timeout = self._default_timeout
        if not self._req.done.wait(timeout):
            raise TimeoutError("generation timed out")
        if self._req.error is not None:
            raise self._req.error
        need = with_logits or (with_logits is None and not self._req.out)
        return {
            "tokens": list(self._req.out),
            "prefill_logits": (self._req.resolve_prefill_logits()
                               if need else self._req.prefill_logits),
            "ttft_s": self._req.ttft_s,
            "finish_reason": self._req.finish_reason,
        }

    @property
    def ttft_s(self) -> float | None:
        return self._req.ttft_s


def _check_hybrid(**using) -> None:
    """A ``mixer_types`` model holds recurrent state and compressed keys
    beside its K/V. Everything named here moves, shares or reads K/V
    alone and would serve such a model wrongly, so each is refused by
    name at construction (ROADMAP Queue 2, M4, is what would lift them)."""
    refused = {
        "kv_layout": "the dense KV layout (kv_layout='dense')",
        "prefix_cache_slots": "the prefix cache (prefix_cache_slots): a "
                              "hit would skip the prefix's recurrent state",
        "speculative_k": "speculative decoding (speculative_k): "
                         "verify_step cannot roll recurrent state back",
        "kv_dtype": "int8 KV (kv_dtype='int8')",
        "kv_fused": "the fused block-table kernel (kv_fused)",
        "tp_shards": "tensor parallelism (tp_shards > 1)",
        "cp_shards": "context parallelism (cp_shards > 1)",
        "pp_stages": "pipeline parallelism (pp_stages > 1)",
        "host_kv_bytes": "stream suspension to the host tier "
                         "(_suspend_stream, host_kv_bytes)",
        "role": "the prefill/decode handoff (role: export_blocks / "
                "import_blocks carry K/V only)",
        "kv_directory": "the fleet KV economy (kv_directory / cold_store: "
                        "export_blocks carries K/V only)",
    }
    for option, on in using.items():
        if on:
            raise ValueError(
                f"mixer_types: {refused[option]} is not supported for a "
                "model with recurrent state and compressed keys")


def _check_looped(**using) -> None:
    """A looped stack (``n_passes`` > 1) holds K/V of every pass and runs
    its layers several times a token. What shards the stack by layer
    ranges is refused by name at construction, and so is what exports
    K/V blocks to another holder (the payloads carry ``cache_layers``
    layers and import as they are, but no test has moved a looped
    model's). Everything else that sizes or moves K/V does so by
    ``cfg.cache_layers``: both layouts, int8 KV, the fused read, the
    prefix cache, speculation, chunked admission, tensor parallelism
    (tests/test_looped_stack.py serves four passes through each)."""
    refused = {
        "pp_stages": "pipeline parallelism (pp_stages > 1): a stage would "
                     "hold a range of layers that every pass runs again",
        "cp_shards": "context parallelism (cp_shards > 1): the ring read "
                     "has not been run over a looped stack's cache layers",
        "host_kv_bytes": "stream suspension to the host tier "
                         "(host_kv_bytes)",
        "role": "the prefill/decode handoff (role)",
        "kv_directory": "the fleet KV economy (kv_directory / cold_store)",
    }
    for option, on in using.items():
        if on:
            raise ValueError(
                f"n_passes > 1: {refused[option]} is not supported for a "
                "stack that runs several times a token")


def _check_latent(**using) -> None:
    """A latent-attention model holds one latent row a token and layer,
    which every head reads, where every other model holds K and V a head.
    Everything named here moves, shares, shards or quantizes K/V by head,
    or has never been run over a latent cache, and would serve such a
    model wrongly or not at all, so each is refused by name at
    construction (ROADMAP Queue 2, M1 and M3, is what would lift them)."""
    refused = {
        "kv_layout": "the paged KV layout (kv_layout='paged': the block "
                     "pool holds K and V a head)",
        "prefix_cache_slots": "the prefix cache (prefix_cache_slots: its "
                              "pool holds K and V a head)",
        "speculative_k": "speculative decoding (speculative_k: the verify "
                         "pass reads K and V a head)",
        "kv_dtype": "int8 KV (kv_dtype='int8')",
        "kv_fused": "the fused block-table kernel (kv_fused)",
        "tp_shards": "tensor parallelism (tp_shards > 1: the store shards "
                     "by KV head, and a latent row has none)",
        "cp_shards": "context parallelism (cp_shards > 1)",
        "pp_stages": "pipeline parallelism (pp_stages > 1)",
        "host_kv_bytes": "stream suspension to the host tier "
                         "(host_kv_bytes)",
        "role": "the prefill/decode handoff (role: export_blocks / "
                "import_blocks carry pool blocks)",
        "kv_directory": "the fleet KV economy (kv_directory / cold_store)",
    }
    for option, on in using.items():
        if on:
            raise ValueError(
                f"kv_lora_rank: {refused[option]} is not supported for a "
                "model with latent attention")


def _weights_footprint(params) -> tuple[int, str]:
    """(bytes of every leaf of a serving tree, dtype of its matrices)."""
    nbytes = sum(int(leaf.nbytes) for leaf in jax.tree.leaves(params))
    return nbytes, str(params["embed"]["kernel"].dtype)


class ContinuousDecoder:
    """Owns the device decode state and the scheduler thread.

    ``prefill_len`` fixes the compiled prompt shape (prompts are right-padded
    to it); ``slots`` is the decode concurrency; total cache length is
    ``prefill_len + max_new_tokens``.
    """

    def __init__(self, params, cfg, *, slots: int, prefill_len: int,
                 max_new_tokens: int, top_k: int = 0,
                 eos_id: int | None = None, seed: int = 0,
                 chunk_size: int = 1, prefix_cache_slots: int = 0,
                 prefix_cache_min_len: int = 16,
                 prefill_len_buckets: int = 0, speculative_k: int = 0,
                 draft_mode: str = "ngram", kv_layout: str = "dense",
                 kv_block_size: int = 16, kv_pool_blocks: int = 0,
                 kv_low_watermark: int = 0, kv_dtype: str = "fp",
                 kv_fused: bool = False,
                 stream_timeout_s: float = 60.0,
                 role: str = "", tp_shards: int = 1,
                 qos: QosPolicy | None = None,
                 host_kv_bytes: int = 0,
                 hol_bypass_limit: int = 4,
                 hol_shield_rounds: int = 8,
                 prefill_chunk_tokens: int = 0,
                 max_prompt_len: int = 0,
                 cp_shards: int = 1,
                 pp_stages: int = 1,
                 kv_directory=None,
                 cold_store=None,
                 peer_fetch=None,
                 kv_import_crossover_tokens: int = 0,
                 kv_affinity_tokens: int = 0,
                 replica_name: str = "",
                 boot_weights_version: int = 0,
                 compile_cache_dir: str = ""):
        # Model-parallel serving: tp_shards > 1 runs THIS replica's
        # decode executables over a tp-wide tensor mesh — weights carry
        # the Megatron column/row split from the model's partition
        # rules, and the KV storage is sharded over the KV-HEAD axis.
        # Block ids index the unsharded block dim, so the allocator,
        # prefix trie, refcount/CoW, and export/import handoff all run
        # unchanged on host-global ids; only bytes-per-token (per-chip
        # HBM) and the fused kernel's read path know about the split.
        # cp_shards > 1 adds a `sequence` axis outside the tensor axis:
        # chunked-prefill attention runs ring-style over it (weights and
        # KV replicated across cp — cp buys PREFILL FLOPs/bandwidth for
        # long prompts, not HBM capacity). pp_stages > 1 adds the
        # outermost `pipeline` axis: the stacked layer weights AND the
        # KV pool's leading layer dim shard over it, so per-chip weight
        # and KV bytes divide by pp while the host-side allocator still
        # sees whole (all-layer) logical blocks.
        self.tp_shards = max(1, int(tp_shards))
        self.cp_shards = max(1, int(cp_shards))
        self.pp_stages = max(1, int(pp_stages))
        if cfg.mixer_types:
            _check_hybrid(
                kv_layout=kv_layout != "paged",
                prefix_cache_slots=prefix_cache_slots > 0,
                speculative_k=speculative_k > 0, kv_dtype=kv_dtype == "int8",
                kv_fused=kv_fused, tp_shards=self.tp_shards > 1,
                cp_shards=self.cp_shards > 1, pp_stages=self.pp_stages > 1,
                host_kv_bytes=host_kv_bytes > 0, role=bool(role),
                kv_directory=(kv_directory is not None
                              or cold_store is not None))
        if cfg.kv_lora_rank:
            _check_latent(
                kv_layout=kv_layout != "dense",
                prefix_cache_slots=prefix_cache_slots > 0,
                speculative_k=speculative_k > 0, kv_dtype=kv_dtype == "int8",
                kv_fused=kv_fused, tp_shards=self.tp_shards > 1,
                cp_shards=self.cp_shards > 1, pp_stages=self.pp_stages > 1,
                host_kv_bytes=host_kv_bytes > 0, role=bool(role),
                kv_directory=(kv_directory is not None
                              or cold_store is not None))
        if cfg.n_passes > 1:
            _check_looped(pp_stages=self.pp_stages > 1,
                          cp_shards=self.cp_shards > 1,
                          host_kv_bytes=host_kv_bytes > 0, role=bool(role),
                          kv_directory=(kv_directory is not None
                                        or cold_store is not None))
        if self.tp_shards > 1:
            if cfg.n_kv_heads % self.tp_shards:
                raise ValueError(
                    f"tp_shards {self.tp_shards} must divide n_kv_heads "
                    f"{cfg.n_kv_heads} (the KV pool shards by head)")
            if cfg.n_heads % self.tp_shards:
                raise ValueError(
                    f"tp_shards {self.tp_shards} must divide n_heads "
                    f"{cfg.n_heads}")
            if cfg.d_ff % self.tp_shards:
                raise ValueError(
                    f"tp_shards {self.tp_shards} must divide d_ff "
                    f"{cfg.d_ff}")
        if self.cp_shards > 1:
            if self.cp_shards & (self.cp_shards - 1):
                raise ValueError(
                    f"cp_shards {self.cp_shards} must be a power of two "
                    "(ring shards ride the pow2 chunk buckets)")
            if kv_layout != "paged":
                raise ValueError("cp_shards > 1 requires kv_layout="
                                 "'paged' (the ring reads the gathered "
                                 "paged span)")
            if kv_fused:
                raise ValueError(
                    "cp_shards > 1 uses the gathered ring read; it does "
                    "not compose with kv_fused")
            if not prefill_chunk_tokens:
                raise ValueError(
                    "cp_shards > 1 shards chunked-prefill attention; "
                    "set prefill_chunk_tokens > 0")
        if self.pp_stages > 1:
            if kv_fused:
                raise ValueError(
                    "pp_stages > 1 does not compose with kv_fused (the "
                    "fused kernel assumes an unsharded layer dim)")
            from kubeflow_tpu.parallel.pipeline import stage_layer_ranges

            # Raises unless n_layers divides evenly; the ranges are the
            # per-stage KV accounting documented in docs/serving.md.
            stage_layer_ranges(cfg.n_layers, self.pp_stages)
            cfg = dataclasses.replace(cfg,
                                      pipeline_stages=self.pp_stages)
        # The one cast of every weight the forward uses at cfg.dtype: a
        # no-op behind the engine (its tree is a serving tree already),
        # the cast for a caller that hands over float32. No dispatch
        # converts a weight after this.
        params = serving_params(params, cfg)
        if self.tp_shards > 1 or self.cp_shards > 1 or self.pp_stages > 1:
            from kubeflow_tpu.models.transformer import partition_rules
            from kubeflow_tpu.parallel.mesh import serving_mesh
            from kubeflow_tpu.parallel.sharding import shard_pytree

            self.mesh = serving_mesh(self.tp_shards, cp=self.cp_shards,
                                     pp=self.pp_stages)
            params = shard_pytree(params, self.mesh, partition_rules(cfg))
        else:
            self.mesh = None
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.prefill_len = prefill_len
        self.max_new_tokens = max_new_tokens
        self.top_k = top_k
        self.eos_id = eos_id
        self.stream_timeout_s = float(stream_timeout_s)
        # Power-of-two prefill length buckets (0 = every prompt pads to
        # prefill_len): a round's prompts ride the smallest allowed
        # compiled shape covering them, so a 6-token prompt stops paying
        # a 128-token prefill. Bucket floor = prefill_len >> buckets.
        self.prefill_len_buckets = max(0, int(prefill_len_buckets))
        # Device-resident prefix KV cache: host trie -> pool row of
        # cached prefix K/V. Admissions that match reuse the rows and
        # prefill only their suffix; finished prompts publish back.
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        self.kv_layout = kv_layout
        # KV residency precision: "fp" keeps the model dtype (bitwise
        # parity with dense pinned in tests); "int8" stores blocks
        # quantized with per-position per-head scales, roughly doubling
        # blocks per HBM byte at a pinned greedy-token tolerance.
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
        if kv_dtype == "int8" and kv_layout != "paged":
            raise ValueError("kv_dtype='int8' requires kv_layout='paged'")
        self.kv_dtype = kv_dtype
        # Fused block-table attention for the paged decode step: the
        # kernel walks the table (int8 dequantized in-register) instead
        # of gathering the dense [slots, total_len] view each step. Off
        # by default — the gather path is the pinned-accuracy reference
        # (bitwise for fp blocks).
        if kv_fused and kv_layout != "paged":
            raise ValueError("kv_fused requires kv_layout='paged'")
        self.kv_fused = bool(kv_fused)
        # Disaggregated-fleet role: "" (colocated, the default),
        # "prefill" (prompt admission only — peers pull finished prompt
        # KV via export_prompt) or "decode" (resumes imported prompts).
        # The handoff rides the paged block pool, so a role requires it.
        if role not in ("", "prefill", "decode"):
            raise ValueError(f"unknown role {role!r}")
        if role and kv_layout != "paged":
            raise ValueError("a fleet role requires kv_layout='paged'")
        self.role = role
        self.prefix_cache = (
            PrefixCache(prefix_cache_slots, min_len=prefix_cache_min_len)
            if prefix_cache_slots > 0 else None
        )
        # Dense layout only: the prefix pool is a second full-width copy
        # of each cached prefix. The paged layout supersedes it — a hit
        # SHARES the donor's pool blocks by refcount (zero device
        # copies), so the main pool is the only KV storage.
        self._prefix_pool = (
            init_prefix_pool(cfg, prefix_cache_slots, prefill_len)
            if prefix_cache_slots > 0 and kv_layout == "dense" else None
        )
        # Guards trie + pool-reference mutation: prime_prefix() runs on
        # caller threads while the scheduler thread matches/publishes.
        self._prefix_lock = threading.Lock()
        # Decode steps fused per device dispatch. 1 = one dispatch per
        # token (finest admission/streaming granularity). K>1 trades
        # admission latency (a new request waits up to K steps) for K×
        # fewer dispatches (tests/test_continuous.py runs both).
        # EOS parking moves on-device inside the fused loop either way.
        self.chunk_size = max(1, int(chunk_size))
        # Long-context serving: prefill_chunk_tokens > 0 admits any
        # prompt whose (post-prefix) suffix exceeds it as a CHAIN of
        # bounded chunk dispatches interleaved with decode rounds — the
        # chunk width is the worst-case gap a long admission can insert
        # into a live stream's inter-token cadence. max_prompt_len
        # raises the prompt ceiling past the compiled prefill width
        # (chunks ride the paged block scatter, so only the virtual row
        # width — not any compiled shape — bounds the prompt).
        self.prefill_chunk_tokens = max(0, int(prefill_chunk_tokens))
        if self.prefill_chunk_tokens:
            if kv_layout != "paged":
                raise ValueError(
                    "prefill_chunk_tokens requires kv_layout='paged' "
                    "(chunks scatter into the block pool)")
            if self.prefill_chunk_tokens > prefill_len:
                raise ValueError(
                    f"prefill_chunk_tokens {self.prefill_chunk_tokens} "
                    f"must be <= prefill_len {prefill_len} (chunks ride "
                    "the compiled suffix buckets)")
        self.max_prompt_len = int(max_prompt_len) or prefill_len
        if self.max_prompt_len < prefill_len:
            raise ValueError(
                f"max_prompt_len {self.max_prompt_len} must be >= "
                f"prefill_len {prefill_len}")
        if self.max_prompt_len > prefill_len and not self.prefill_chunk_tokens:
            raise ValueError(
                f"max_prompt_len {self.max_prompt_len} > prefill_len "
                f"{prefill_len} requires prefill_chunk_tokens > 0 "
                "(monolithic prefill is bounded by the compiled width)")
        self.total_len = self.max_prompt_len + max_new_tokens
        if self.cp_shards > 1:
            floor = (prefill_len >> self.prefill_len_buckets
                     if self.prefill_len_buckets
                     else min(8, prefill_len))
            if floor % self.cp_shards:
                raise ValueError(
                    f"cp_shards {self.cp_shards} must divide the suffix "
                    f"bucket floor {floor} (every chunk dispatch shards "
                    "its query tokens over the sequence axis)")
            if self.total_len % self.cp_shards:
                raise ValueError(
                    f"cp_shards {self.cp_shards} must divide "
                    f"max_prompt_len + max_new_tokens = {self.total_len} "
                    "(the ring streams the gathered virtual row)")
        # Speculative decoding: K>0 turns decode rounds into verify
        # rounds whenever the proposer has drafts — one fused dispatch
        # scores up to K draft tokens per row (chunk_size>1 fuses that
        # many verify steps per dispatch, mirroring decode_chunk).
        self.speculative_k = max(0, int(speculative_k))
        self._verify_steps = self.chunk_size if self.chunk_size > 1 else 1
        self._spec = (
            make_proposer(
                draft_mode, target_vocab=cfg.vocab_size, slots=slots,
                total_len=self.total_len,
                propose_steps=(self._verify_steps * self.speculative_k
                               + self._verify_steps - 1),
                seed=seed)
            if self.speculative_k > 0 else None
        )
        # Per-slot draft length, auto-tuned in [1, speculative_k]: shrink
        # while a row's drafts keep missing (verify compute is then pure
        # overhead), recover on clean sweeps.
        self._slot_k = [self.speculative_k] * slots
        # K/V bytes one resident token costs, PER CHIP (a tp-sharded
        # store holds Hkv / tp heads per position on each chip, and the
        # fill gauges must reflect the HBM a chip actually spends), over
        # the model's CACHE layers, not its depth: only the sparse layers
        # of a mixer_types model hold K/V, and a looped stack holds K/V
        # of every pass.
        self.kv_bytes_per_token = kv_bytes_per_token(
            cfg.cache_layers, cfg.n_kv_heads, cfg.head_dim,
            jnp.dtype(cfg.dtype).itemsize, kv_dtype,
            tp_shards=self.tp_shards,
            latent=cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        if kv_layout == "paged":
            self.kv_block_size = max(1, int(kv_block_size))
            if self.total_len % self.kv_block_size:
                raise ValueError(
                    f"kv_block_size {self.kv_block_size} must divide "
                    f"max_prompt_len + max_new_tokens = {self.total_len} "
                    "(equal virtual row width is what makes paged decode "
                    "byte-identical to dense)")
            mb = self.total_len // self.kv_block_size
            # 0 = worst-case parity with the dense reservation: the pool
            # can back every slot at full length, so paged is never more
            # restrictive than dense. Smaller pools trade that for HBM;
            # larger slots counts then buy real concurrency.
            num_blocks = int(kv_pool_blocks) or slots * mb
            if num_blocks < mb:
                raise ValueError(
                    f"kv_pool_blocks {num_blocks} cannot back even one "
                    f"worst-case sequence ({mb} blocks)")
            self._alloc = BlockAllocator(
                num_blocks, self.kv_block_size,
                bytes_per_token=self.kv_bytes_per_token)
            self._max_blocks_per_seq = mb
            # Host mirror of the device block table; sentinel
            # ``num_blocks`` marks unallocated entries (writes through
            # them are dropped on device).
            self._table = np.full((slots, mb), num_blocks, np.int32)
            self._slot_blocks: list[list[int]] = [[] for _ in range(slots)]
            self._state = init_paged_state(cfg, slots, num_blocks,
                                           self.kv_block_size, mb, seed,
                                           kv_dtype=kv_dtype)
        else:
            self.kv_block_size = int(kv_block_size)
            self._alloc = None
            self._state = init_decode_state(cfg, slots, self.total_len, seed)
        # Per-row state that is not K/V (a mixer_types model's recurrent
        # state and compressed keys), as allocated: the sizes never change.
        self.state_bytes = sum(
            int(leaf.nbytes) for name in ("lin_state", "ckeys")
            for leaf in jax.tree.leaves(self._state.get(name, ())))
        # The sparse layers' selection, for the host's count of what a
        # decode step reads (None: every layer reads its whole context).
        self._sparse = cfg.sparse_spec if cfg.mixer_types else None
        # What the sparse layers' decode read compiles to on this backend
        # at this shape ("" where no layer selects): the kernel that reads
        # the selected blocks in place, or the XLA gather.
        self.sparse_attn_impl = (
            decode_implementation(self._sparse, cfg.head_dim)
            if self._sparse else "")
        # What the dense cache's single-token read compiles to ("" where
        # the K/V lies in a block pool): the kernel that stops at each
        # row's length, or the whole-row XLA read (off the TPU, heads not
        # lane-aligned, and under a mesh, where the kernel could not be
        # partitioned). A latent store's row is its "head".
        cache = self._state.get("cache")
        self.dense_attn_impl = (
            "" if cache is None else "xla" if self.mesh is not None
            else dense_decode_implementation(
                cfg.latent_width if cfg.kv_lora_rank else cfg.head_dim,
                cache["k"].dtype))
        self._admit_rows = max_admit_rows(cfg)
        if self.mesh is not None:
            # KV payload onto the mesh, head-sharded (and layer-sharded
            # over `pipeline` when pp > 1); scalars/tables/RNG
            # replicated. Every jitted step's computation then follows
            # its committed inputs onto the mesh. The `sequence` axis is
            # named nowhere in the state specs — KV replicates across
            # cp, and only the chunked-prefill ring read partitions it.
            pp_axis = "pipeline" if self.pp_stages > 1 else None
            self._state = shard_decode_state(self._state, self.mesh,
                                             pp_axis=pp_axis)
            if self._prefix_pool is not None:
                self._prefix_pool = shard_decode_state(self._prefix_pool,
                                                       self.mesh,
                                                       pp_axis=pp_axis)
        # The fused block-table kernel walks its mesh twin only under a
        # tensor mesh, and the dense cache's kernel stands back for the
        # XLA read under one; the gather path partitions under plain
        # GSPMD.
        self._kmesh = (self.mesh if self.kv_fused or self.dense_attn_impl
                       else None)
        # Ring mesh for chunk dispatches: only cp > 1 routes the chunk's
        # span attention through the sequence-axis ring (decode steps
        # stay on the plain GSPMD path regardless).
        self._ring = self.mesh if self.cp_shards > 1 else None
        self.kv_low_watermark = max(0, int(kv_low_watermark))
        # Multi-tenant QoS: token-bucket admission at submit, weighted-
        # fair/priority/aging ordering of the pending queue, deadline
        # shedding, and suspension of low-priority live streams under
        # memory pressure (requires the host tier below to park KV).
        self.qos = qos
        # Host-RAM KV tier (HBM -> host): trie evictions demote their
        # blocks here instead of freeing outright, trie misses probe it
        # before cold prefill, and suspended streams pin their exported
        # KV here until resume. 0 disables.
        if host_kv_bytes and kv_layout != "paged":
            raise ValueError("host_kv_bytes requires kv_layout='paged'")
        self._host_tier = (HostKvTier(int(host_kv_bytes))
                           if host_kv_bytes else None)
        # Host-global bytes one tiered token costs (the tier holds the
        # gathered, unsharded payload even under tp), every cache layer.
        self._host_bytes_per_token = (
            kv_bytes_per_token(cfg.cache_layers, cfg.n_kv_heads,
                               cfg.head_dim, jnp.dtype(cfg.dtype).itemsize,
                               kv_dtype)
            if self._alloc is not None else 0)
        # Fleet KV economy (HBM -> host -> PEER -> COLD): the shared
        # prefix->holder directory (serving/kv_directory.py), the
        # content-addressed cold store (serving/cold_store.py), and the
        # peer-pull callable the fleet/server wires in
        # (``peer_fetch(holder, tokens, version) -> {"envelope": packed,
        # "weights_version": v} | None``). A local trie+host miss probes
        # the directory ON THE CALLER THREAD in submit() — never under
        # a decoder lock — and installs the imported prefix so the
        # pop-time plan sees an ordinary trie hit.
        # ``kv_import_crossover_tokens`` is the recompute-vs-import
        # crossover: the per-pull fixed cost (RTT + envelope
        # pack/unpack + scatter dispatch) amortizes over matched
        # tokens, so importing pays only when the remote match beats
        # the best LOCAL tier by at least this many tokens (0 = any
        # strictly-deeper match imports).
        if (kv_directory is not None or cold_store is not None) \
                and kv_layout != "paged":
            raise ValueError(
                "the fleet KV economy (kv_directory/cold_store) "
                "requires kv_layout='paged'")
        self.kv_directory = kv_directory
        self.cold_store = cold_store
        self._peer_fetch = peer_fetch
        self.replica_name = str(replica_name or "")
        self.kv_import_crossover_tokens = max(
            0, int(kv_import_crossover_tokens))
        self.kv_affinity_tokens = (int(kv_affinity_tokens)
                                   or DEFAULT_AFFINITY_TOKENS)
        # Head-of-line bypass: how many memory-blocked candidates a
        # round may skip past looking for a smaller request that fits,
        # and how many blocked rounds age a head into an unskippable
        # shield (so bypass can never starve the big request).
        self.hol_bypass_limit = max(0, int(hol_bypass_limit))
        self.hol_shield_rounds = max(1, int(hol_shield_rounds))
        # Serializes device access to self._state between the scheduler
        # thread and caller-thread prime_prefix (which, in paged mode,
        # writes primed blocks into the SHARED pool — the jitted calls
        # donate state buffers, so unsynchronized access would read
        # donated storage).
        self._state_lock = threading.Lock()
        self._slot_req: list[_Request | None] = [None] * slots
        self._active_count = 0
        self._pending: deque[_Request] = deque()
        # In-flight chunked admissions: (req, slot) in arrival order.
        # Scheduler-thread-only writes; the pop loop advances the OLDEST
        # job by exactly one chunk per round, so a long admission never
        # inserts more than one chunk between decode dispatches.
        self._chunk_jobs: list[tuple[_Request, int]] = []
        self._cv = threading.Condition()
        self._stopped = False
        # Serving metrics (scraped via the model server's /monitoring route).
        self.tokens_emitted = 0
        self.steps = 0       # device decode steps (incl. masked chunk tail)
        self.dispatches = 0  # decode dispatches (host→device→host)
        self.prefill_dispatches = 0  # admission round-trips (fused)
        self.admitted = 0            # requests admitted
        self.prefill_tokens = 0      # real prompt tokens actually prefilled
        self.prefill_chunks = 0      # interior chunk dispatches (long prompts)
        self.prompt_rejected_too_long = 0  # PromptTooLong terminal rejections
        # Prefix-cache counters (all zero when the cache is disabled).
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_reused = 0   # prompt tokens served from the pool
        self.prefix_suffix_tokens = 0   # suffix tokens prefilled on hits
        self.prefix_inserts = 0         # prefixes published to the pool
        # Speculative-decoding counters (zero when speculation is off).
        self.spec_drafted_tokens = 0    # draft tokens submitted to verify
        self.spec_accepted_tokens = 0   # draft tokens the target kept
        self.spec_verify_dispatches = 0  # fused verify round-trips
        self.ttft_sum = 0.0
        self.ttft_count = 0
        # Paged-KV counters (zero in the dense layout).
        self.kv_cow_copies = 0       # tail-block copy-on-writes
        self.kv_shared_blocks = 0    # blocks mapped by refcount on hits
        self.kv_defer_admissions = 0  # rounds deferred for memory
        # Disaggregated handoff counters (zero outside a role split).
        self.kv_handoff_exports = 0   # prompts exported to a decode peer
        self.kv_handoff_imports = 0   # prompts imported from a prefill peer
        self.kv_handoff_tokens = 0    # prefix tokens that rode a handoff
        # Tiered-KV / QoS counters (zero when the features are off).
        self.kv_suspends = 0          # live streams parked to the host tier
        self.kv_resumes = 0           # parked streams re-admitted
        self.kv_host_hits = 0         # trie misses served by the host tier
        # Fleet KV-economy counters (zero without a directory/cold store).
        self.kv_peer_hits = 0         # prefixes imported from a peer replica
        self.kv_peer_misses = 0       # probes that found nothing importable
        self.kv_peer_import_bytes = 0  # payload bytes pulled from peers
        self.kv_peer_fetch_failures = 0  # dead holder / refused pull
        self.kv_cold_hits = 0         # prefixes imported from the cold store
        self.kv_cold_demotions = 0    # host evictions packed into cold
        self.kv_cold_import_bytes = 0  # payload bytes promoted from cold
        self.kv_import_stale_refused = 0  # envelopes refused: stale epoch
        self.kv_import_skipped_crossover = 0  # gains under the threshold
        self.kv_directory_publishes = 0  # holder hints this replica wrote
        self.qos_deadline_shed = 0    # requests shed past their deadline
        self.hol_bypasses = 0         # admissions that jumped a blocked head
        # Decode service per tenant (tokens emitted) — the weighted-fair
        # ordering's used-share input. Guarded by _mlock with the other
        # counters.
        self._tenant_served: dict[str, float] = {}
        # Sparse-attention counters (zero without mixer_types), one count
        # per row per decode step, from the lengths the host has.
        self.sparse_tokens_attended = 0    # tokens the selection read
        self.sparse_tokens_in_context = 0  # tokens the rows held
        self.rows_dense = 0                # row-steps at or under dense_len
        self.rows_sparse = 0               # row-steps over it
        # Looped-stack and latent-attention counter (zero otherwise): per
        # emitted token the length of its row, i.e. the tokens each of the
        # step's cache layers attended, from the lengths the host has.
        # Such a model routes tokens through _dispatch_looped; a plain
        # model's round runs no statement of this.
        self.kv_tokens_attended = 0
        if cfg.n_passes > 1 or cfg.kv_lora_rank:
            self._dispatch = self._dispatch_looped
        self.kv_blocks_peak = 0      # high-water blocks_in_use
        self.peak_in_flight = 0      # high-water concurrent requests
        # Counter mutations and metrics() reads go through this lock so
        # derived ratios (ttft_avg_s, spec_acceptance_rate) are computed
        # from a CONSISTENT snapshot, never from a torn sum/count pair
        # mid-update. Leaf lock: never acquired while holding it.
        self._mlock = threading.Lock()
        # Latency *distributions* (the autoscaler/scheduler signals
        # averages can't carry): TTFT, inter-token gap, device dispatch
        # duration by kind, queue wait, and per-dispatch batch occupancy.
        # Rendered by the model server's /monitoring exposition; quantile
        # estimates surface in metrics() (p50/p90/p99).
        self.registry = MetricRegistry()
        self._h_ttft = self.registry.histogram(
            "serving_ttft_seconds", "Submit to first emitted token")
        self._h_itl = self.registry.histogram(
            "serving_inter_token_seconds",
            "Host-side gap between a stream's token arrivals")
        self._h_queue_wait = self.registry.histogram(
            "serving_queue_wait_seconds",
            "Submit to slot admission (includes memory deferrals)")
        # Per-tenant queue wait: tenant ids are hash-bucketed into a
        # BOUNDED label set (qos.tenant_bucket) — raw ids are
        # client-controlled and would explode exposition cardinality.
        self._h_tenant_wait = self.registry.histogram(
            "serving_tenant_queue_wait_seconds",
            "Submit to slot admission, by hash-bucketed tenant",
            labels=("tenant",))
        self._h_dispatch = self.registry.histogram(
            "serving_dispatch_seconds",
            "Device round-trip duration", labels=("kind",))
        occ_bounds, b = [], 1
        while b < slots:
            occ_bounds.append(b)
            b *= 2
        occ_bounds.append(slots)
        self._h_occupancy = self.registry.histogram(
            "serving_batch_occupancy",
            "Active slots per decode dispatch", buckets=occ_bounds)
        # Role label on the exposition so per-pool dashboards and the
        # operator's scrape can tell prefill from decode replicas
        # without inspecting Deployment names.
        self.registry.gauge(
            "serving_role",
            "Replica role in a disaggregated fleet (1 = this role)",
            labels=("role",)).labels(self.role or "colocated").set(1)
        self.registry.gauge(
            "serving_tp_shards",
            "Tensor-parallel mesh width of this replica (1 = "
            "single-chip)").set(self.tp_shards)
        self.registry.gauge(
            "serving_cp_shards",
            "Context-parallel (sequence-axis) width of this replica's "
            "chunked-prefill ring (1 = no ring)").set(self.cp_shards)
        self.registry.gauge(
            "serving_pp_stages",
            "Pipeline-parallel stages sharding this replica's layer "
            "stack and KV pool (1 = unsplit)").set(self.pp_stages)
        self._c_prefill_chunks = self.registry.counter(
            "serving_prefill_chunks_total",
            "Chunked-prefill dispatches (interior chunks of long "
            "admissions; the final chunk counts as a prefill)")
        self._h_prefill_chunk = self.registry.histogram(
            "serving_prefill_chunk_seconds",
            "Chunked-prefill dispatch duration (one interior chunk)")
        # Live weight streaming (update_weights): monotonically
        # increasing weights epoch, push counter, and the end-to-end
        # push duration (device placement + atomic swap + stale flush).
        # A peer-born replica stamps its donor's epoch at construction
        # (boot_weights_version) so the rollout machinery and the
        # stale-KV fences see a version-consistent fleet from birth.
        self.weights_version = max(0, int(boot_weights_version))
        self.weight_pushes = 0
        self.weight_stale_refused = 0  # stale trie/tier hits refused
        self.last_swap_seconds = 0.0   # last push's in-lock swap stall
        self._g_weights_version = self.registry.gauge(
            "serving_weights_version",
            "Weights epoch installed by live pushes (0 = boot weights)")
        if self.weights_version:
            self._g_weights_version.set(self.weights_version)
        # What the installed tree holds on the device: the witness that
        # serving keeps one copy at the compute dtype (a float32 tree
        # would read twice the bytes). Set here and at every swap.
        self._g_weights_bytes = self.registry.gauge(
            "serving_weights_bytes",
            "Bytes of the installed serving parameter tree")
        self._g_weights_bytes.set(_weights_footprint(self.params)[0])
        # 1 when the stacked q/k/v are held in the layout the decode dot
        # reads (serving_params), 0 for a tree that has none (a mixer
        # model's per-layer trees): read from the tree, which a push
        # cannot change.
        layers = self.params["layers"]
        self.qkv_held_transposed = int(isinstance(layers, dict)
                                       and "wq_t" in layers["attn"])
        self.registry.gauge(
            "serving_qkv_held_transposed",
            "1 when the stacked q/k/v are held transposed for the "
            "decode dot").set(self.qkv_held_transposed)
        self._c_weight_pushes = self.registry.counter(
            "serving_weight_pushes_total",
            "Live weight swaps installed by update_weights")
        self._h_weight_push = self.registry.histogram(
            "serving_weight_push_seconds",
            "update_weights duration: device placement, atomic swap, "
            "stale-KV flush")
        # Per-stream lifecycle timelines, bounded ring, served at the
        # model server's /debug/requests (JSON + chrome-trace export).
        self.trace = TraceStore()
        # Scheduler rounds begun (one per pass of _run's loop): the number
        # every sched.* span carries, and a request's ``admitted`` and
        # ``first_token`` timeline events with it.
        self._round = 0
        phase_seconds = self.registry.counter(
            PHASE_COUNTER,
            "Scheduler-thread seconds by phase of a round: idle (waiting "
            "for work), plan (under the queue lock), build (host arrays "
            "for a dispatch), dispatch (enqueue of the jitted call), fetch "
            "(device_get), route (tokens to streams, finishes)",
            labels=("phase",))
        self._c_phase = {p: phase_seconds.labels(p) for p in SCHED_PHASES}
        # One record a round (tracing.RoundRecord), the scheduler thread's
        # alone: the newest in ``rounds``' rings, served at the model
        # server's /debug/rounds beside ``trace``; the slow ones counted
        # by the phase that held them up.
        self.rounds = RoundLog(self.registry.counter(
            SLOW_COUNTER,
            "Scheduler rounds that took over 4x the median of the recent "
            "rounds of their kind and over it by 25 ms, by the phase "
            "that held most of the excess (other: under no phase)",
            labels=("phase",)))
        self._rec = RoundRecord(0, 0, 0.0, 0.0, 0.0)
        # Step dispatches enqueued since construction (admission, chunk,
        # verify, draft, decode: one XLA module execution each): the
        # ordinal a sched.dispatch span carries as ``launch``, and the
        # sched.fetch that waits for its result.
        self._launches = 0
        # A chunk or a suspension went to the device since the last decode
        # step was enqueued: the next step's tokens reach their streams
        # late by it.
        self._behind = False
        self._ramp_streak = 0  # consecutive admission-only rounds
        # The plain decode step whose tokens are still on the device:
        # (tokens, emitted mask, time of its dispatch, its launch ordinal,
        # whether it was enqueued behind a chunk or a suspension), or None.
        self._inflight = None
        if self.prefix_cache is not None and self._alloc is not None:
            # Trie evictions must return the entry's refcounted blocks
            # to the pool; remove() fires this under the prefix lock.
            self.prefix_cache.on_evict = self._drop_entry_blocks
        if self._host_tier is not None:
            # Host-tier observability (the directory publish path must
            # be visible to size the tier): eviction-age distribution;
            # occupancy/high-water ride metrics() gauges.
            self._h_host_evict_age = self.registry.histogram(
                "serving_kv_host_eviction_age_seconds",
                "Idle time a demoted payload sat in the host tier "
                "before LRU pressure evicted it")
            self._host_tier.eviction_age_observe = \
                self._h_host_evict_age.observe
            if self.cold_store is not None:
                # The economy's demotion chain: host-tier evictions
                # pack into the cold store (and publish the hint)
                # BEFORE the bytes drop.
                self._host_tier.on_evict = self._demote_to_cold
        # Newborn ramp state: a birth path (model server boot, fleet
        # add_replica) sets `warming` True before calling warm(); the
        # fleet admits a warming member via least-loaded spill only —
        # no affine share — and /healthz reports "warming" so the
        # gateway excludes it without penalty. Defaults False: a
        # decoder constructed outside a birth path serves immediately.
        self.warming = False
        self.compile_cache_hits = 0
        self.compile_cache_misses = 0
        self.warm_seconds = 0.0
        self.warm_failed_shapes = 0
        self.compile_cache = None
        if compile_cache_dir:
            from kubeflow_tpu.serving.compile_cache import CompileCache
            self.compile_cache = CompileCache(compile_cache_dir)
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------

    def engine_fingerprint(self) -> str:
        """Digest keying this decoder's compiled dispatch set in the
        persistent compile cache (see serving/compile_cache.py)."""
        from kubeflow_tpu.serving.compile_cache import engine_fingerprint
        return engine_fingerprint(
            self.cfg, tp_shards=self.tp_shards, cp_shards=self.cp_shards,
            pp_stages=self.pp_stages, kv_layout=self.kv_layout,
            kv_dtype=self.kv_dtype, kv_fused=self.kv_fused,
            kv_block_size=getattr(self, "kv_block_size", 0),
            slots=self.slots, prefill_len=self.prefill_len,
            prefill_len_buckets=self.prefill_len_buckets,
            chunk_size=self.chunk_size,
            speculative_k=self.speculative_k,
            prefill_chunk_tokens=self.prefill_chunk_tokens,
            max_prompt_len=self.max_prompt_len, top_k=self.top_k)

    def dispatch_keys(self) -> list[str]:
        from kubeflow_tpu.serving.compile_cache import dispatch_keys
        return dispatch_keys(
            slots=self.slots, prefill_len=self.prefill_len,
            prefill_len_buckets=self.prefill_len_buckets,
            chunk_size=self.chunk_size,
            speculative_k=self.speculative_k,
            prefill_chunk_tokens=self.prefill_chunk_tokens)

    def warm(self, compile_cache=None) -> dict:
        """Pre-compile the full dispatch set by running dummy
        generations through the real submit path — one admission per
        prefill bucket, decode steps at the chunk width, the verify
        shape under speculation, and the chunked-prefill interior shape
        for long prompts. Populates the in-process jit cache and XLA's
        persistent store; the manifest accounting splits the set into
        hits (a prior same-fingerprint replica already compiled them —
        this birth deserializes) vs misses (compiled here, recorded for
        the next birth). Flips ``warming`` off at the end — the
        fleet/gateway ramp gate.

        Does not raise: a newborn that cannot warm one shape (QoS rate
        limit on the dummy tenant, a compile the device refuses) still
        comes up. But a shape that failed is COUNTED and REPORTED —
        ``failed`` / ``failed_shapes`` / ``first_error`` in the return
        value, ``warm_failed_shapes`` in :meth:`metrics` — and only the
        dispatch keys whose dummy generation actually ran are booked as
        cache coverage. The model server treats any failed shape as a
        failed boot.
        """
        t0 = time.perf_counter()
        cache = compile_cache if compile_cache is not None \
            else self.compile_cache
        floor = (self.prefill_len >> self.prefill_len_buckets
                 if self.prefill_len_buckets else self.prefill_len)
        # (admit key, prompt length) per dummy generation. Every one
        # also drives the decode (and, under speculation, verify) step;
        # one longer than the chunk width drives the chunk executable.
        plan, w = [], max(1, floor)
        while True:
            plan.append((f"admit:s{w}", max(1, min(w, self.max_prompt_len))))
            if w >= self.prefill_len:
                break
            w *= 2
        if self.prefill_chunk_tokens and self.max_prompt_len \
                > self.prefill_len:
            plan.append((None, min(
                self.max_prompt_len,
                self.prefill_len + self.prefill_chunk_tokens)))
        steps = max(1, min(self.max_new_tokens, self.chunk_size))
        failed: list[tuple[str, str]] = []
        handles = []
        for key, n in plan:
            # Distinctive token pattern: repeated so the ngram proposer
            # drafts (driving the verify executable), and unlikely to
            # alias real prompts in the prefix trie.
            prompt = ([7, 11, 13] * (n // 3 + 1))[:n]
            try:
                handles.append((key, n, self.submit(prompt, steps)))
            except Exception as e:  # boundary: reported, not raised
                failed.append((key or f"prompt:{n}",
                               f"{type(e).__name__}: {e}"))
        ran, ran_lens = [], []
        for key, n, h in handles:
            try:
                h.result()
            except Exception as e:  # boundary: reported, not raised
                failed.append((key or f"prompt:{n}",
                               f"{type(e).__name__}: {e}"))
            else:
                ran_lens.append(n)
                if key:
                    ran.append(key)
        if ran_lens:
            chunked = (self.prefill_chunk_tokens
                       and max(ran_lens) > self.prefill_chunk_tokens)
            ran += [k for k in self.dispatch_keys()
                    if k.startswith(("decode:", "verify:"))
                    or (chunked and k.startswith("chunk:"))]
        hits = misses = 0
        if cache is not None and ran:
            hits, misses = cache.account(self.engine_fingerprint(), ran)
        secs = time.perf_counter() - t0
        with self._mlock:
            self.compile_cache_hits += hits
            self.compile_cache_misses += misses
            self.warm_seconds = secs
            self.warm_failed_shapes = len(failed)
        self.warming = False
        return {"seconds": secs, "hits": hits, "misses": misses,
                "keys": len(self.dispatch_keys()),
                "failed": len(failed),
                "failed_shapes": [k for k, _ in failed],
                "first_error": failed[0][1] if failed else None}

    def weights_snapshot(self):
        """Consistent (params, weights_version) pair for a donor-side
        peer pull: pointer reads under the state lock (no copies, no
        blocking work) — the same discipline update_weights' swap uses,
        so a puller never sees epoch N's version with epoch N+1's
        pytree."""
        with self._state_lock:
            return self.params, self.weights_version

    def submit(self, tokens: list[int], max_new_tokens: int,
               temperature: float = 0.0, *,
               request_id: str | None = None, tenant: str = "",
               priority: int | None = None,
               deadline_ms: float = 0.0) -> StreamHandle:
        """``tenant``/``priority``/``deadline_ms`` are the QoS surface
        (threaded from the gateway's X-Tenant/X-Priority/X-Deadline-Ms
        headers). With a QoS policy configured, the tenant's token
        bucket gates this call (raises
        :class:`~kubeflow_tpu.serving.qos.QosRejected` -> HTTP 429 with
        Retry-After), the pop loop orders by weighted fair share +
        aged priority, and a request still queued past its deadline is
        shed instead of served."""
        if self.qos is not None:
            # Raises QosRejected when the tenant's bucket is empty —
            # BEFORE the request enters the queue, so overload degrades
            # to fast 429s instead of queue collapse.
            self.qos.admit(tenant, time.perf_counter())
        if len(tokens) > self.max_prompt_len:
            # Terminal, not truncation: silently dropping the prompt
            # tail would serve an answer to a question the caller never
            # asked. max_prompt_len is the replica's hard ceiling
            # (chunking already lifted it past the compiled prefill
            # width) — beyond it the request is a 413, like any body
            # the server cannot represent.
            with self._mlock:
                self.prompt_rejected_too_long += 1
            raise PromptTooLong(
                f"prompt is {len(tokens)} tokens but this replica "
                f"serves at most {self.max_prompt_len} "
                f"(max_prompt_len; prefill_chunk_tokens="
                f"{self.prefill_chunk_tokens})")
        req = _Request(tokens=list(tokens),
                       want=min(max_new_tokens, self.max_new_tokens),
                       temperature=float(temperature))
        req.tenant = tenant or DEFAULT_TENANT
        req.priority = (self.qos.base_priority(tenant, priority)
                        if self.qos is not None else int(priority or 0))
        if deadline_ms and deadline_ms > 0:
            req.deadline_t = req.submit_t + float(deadline_ms) / 1e3
        # Lifecycle timeline, keyed by the propagated X-Request-ID (or a
        # fresh one): submit marks t=0, queued marks entry to the pending
        # deque — every later phase hangs off these two anchors.
        req.timeline = self.trace.start(request_id)
        req.request_id = req.timeline.request_id
        req.timeline.event("submit", prompt_tokens=len(req.tokens),
                           want=req.want, tenant=req.tenant,
                           priority=req.priority)
        if self.kv_directory is not None or self.cold_store is not None:
            # Fleet miss-path probe (trie -> host -> peer -> cold) on
            # the CALLER's thread, before the request enters the queue:
            # the pop loop plans prefixes under the scheduler condition,
            # where a blocking peer fetch would stall every submit. A
            # successful import lands in the trie, so pop-time planning
            # sees an ordinary local hit. Probes are best-effort — an
            # import failure must never fail the submit it was trying
            # to speed up.
            try:
                self._maybe_import_remote(req.tokens, req.timeline)
            except Exception:
                pass
        with self._cv:
            if self._stopped:
                req.timeline.close(error=RuntimeError("decoder is stopped"))
                raise RuntimeError("decoder is stopped")
            self._pending.append(req)
            req.timeline.event("queued", depth=len(self._pending))
            self._cv.notify()
        return StreamHandle(req, self.stream_timeout_s)

    def generate(self, tokens: list[int], max_new_tokens: int,
                 temperature: float = 0.0,
                 timeout: float | None = None, **submit_kw) -> dict:
        return self.submit(tokens, max_new_tokens, temperature,
                           **submit_kw).result(timeout)

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            # Snapshot under the cv: the scheduler thread may be
            # mid-pop, and join() below can time out — after which
            # iterating the live deque would race its popleft.
            queued = list(self._pending)
            self._cv.notify()
        self._thread.join(timeout=5)
        err = RuntimeError("decoder stopped")
        for req in queued + self._slot_req:
            if req is not None and not req.done.is_set():
                self._finish(req, error=err)
        self.rounds.report_summary()

    # ------------------------------------------------------------------

    def _finish(self, req: _Request, *, reason: str = "length",
                error: Exception | None = None) -> None:
        # Idempotent: the crash path (_fail_all) sweeps everything still
        # live on loop exit, racing stop() and the inner error handler —
        # first finisher wins, later calls are no-ops.
        if req.done.is_set():
            return
        # A suspended request dying (deadline shed, stop, loop death)
        # must drain its pinned host-tier payload — pinned bytes are
        # exempt from LRU pressure, so nothing else ever reclaims them.
        if req.host_key is not None and self._host_tier is not None:
            with self._prefix_lock:
                self._host_tier.discard(req.host_key)
            req.host_key = None
        req.error = error
        req.finish_reason = reason if error is None else "error"
        if req.timeline is not None:
            # Every finish path funnels here, so a closed request can
            # never leak an open timeline — the invariant the chaos
            # (_fail_all) test pins. The decode phase is the one span
            # first_token→finish; its size rides the terminal event.
            rounds = self._round - req.admit_round + 1 \
                if req.admit_round else 0
            req.timeline.close(req.finish_reason, error=error,
                               tokens=len(req.out), rounds=rounds)
        req.stream.put(_DONE)
        req.done.set()

    # -- paged-KV bookkeeping (no-ops in the dense layout) -------------

    def _drop_entry_blocks(self, entry) -> None:
        """Prefix-trie eviction hook: DEMOTE the entry's blocks to the
        host tier (HBM -> host, verbatim bytes), then release the
        refcounted blocks. Called by PrefixCache.remove() with the
        prefix lock held — must not re-acquire it."""
        if self._host_tier is not None and entry.blocks:
            self._demote_entry(entry)
        for b in (entry.blocks or ()):
            self._alloc.free(b)

    def _demote_entry(self, entry) -> None:
        """Export an evicted entry's blocks into the host tier so a
        later miss gets a second chance instead of a cold prefill.
        Runs under the prefix lock (the eviction path itself); the
        export's device fetch MUST complete before the blocks return
        to the free list below us, so this is the one spot the
        eviction path pays a device round-trip — the price of
        demoting instead of destroying."""
        # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; swap flush re-sweeps
        if entry.version != self.weights_version:
            return  # stale epoch: destroying beats a poisoned second chance
        plen = min(len(entry.key), len(entry.blocks) * self.kv_block_size)
        key = tuple(entry.key[:plen])
        if plen < 1 or self._host_tier.has(key):
            return
        est = (self._alloc.blocks_for(plen) * self.kv_block_size
               * self._host_bytes_per_token)
        if not self._host_tier.can_fit(est):
            return  # pinned suspensions own the budget; skip the copy
        ids = list(entry.blocks[: self._alloc.blocks_for(plen)])
        try:
            payload = self._export_ids(ids)
        except Exception:
            # A dead/poisoned device state must not wedge the eviction
            # path (the crash drain evicts the whole trie): losing the
            # second-chance copy is fine, losing the free() is a leak.
            return
        if self._host_tier.put(key, payload, plen,
                               version=entry.version):
            self._publish_directory(key, plen, entry.version,
                                    tier="host")

    def _set_table_row(self, slot: int, blocks: list[int]) -> None:
        """Point ``slot``'s host block-table row at ``blocks`` (sentinel
        beyond them); uploaded to device at the next admission call."""
        self._table[slot, :] = self._alloc.num_blocks
        self._table[slot, : len(blocks)] = blocks

    def _free_slot_blocks(self, slot: int) -> None:
        """Return a retiring slot's block references to the allocator.
        Idempotent — the crash path can race the normal finish path, and
        only the first call finds blocks to free."""
        if self._alloc is None:
            return
        with self._prefix_lock:
            # tpu-lint: disable=lock-inconsistent-guard -- scheduler-thread-owned slot state
            blocks, self._slot_blocks[slot] = self._slot_blocks[slot], []
            for b in blocks:
                self._alloc.free(b)
            if blocks:
                # tpu-lint: disable=lock-inconsistent-guard -- row arms under own dispatch (PR-8)
                self._table[slot, :] = self._alloc.num_blocks

    def _reclaim_blocks(self, need: int, timeline=None) -> None:
        """Evict unpinned prefix-cache entries (LRU first) until ``need``
        blocks are free — cache-held blocks are reclaimable memory, not
        reservations, so admission pressure beats cold cache entries.
        Caller holds the prefix lock. Evictions forced by an admission
        land on that request's timeline."""
        if self.prefix_cache is None:
            return
        evicted = 0
        while self._alloc.free_blocks < need:
            if not self.prefix_cache.evict_lru():
                break
            evicted += 1
        if evicted and timeline is not None:
            timeline.event("kv_evict", entries=evicted)

    def _admit_batch(self, pending: list[tuple[_Request, int]]) -> None:
        """Admit a round's pending requests in ONE dispatch that fuses
        prefill, state insert, AND one decode step
        (:func:`admit_rows_and_step`) — the new requests' first token
        ships on the admission round-trip itself.

        The batch is padded up to a power-of-two bucket in BOTH
        dimensions (bounding the number of compiled prefill shapes):
        batch rows by repeating the last real admission verbatim
        (duplicate scatter indices with identical payloads are
        deterministic, so padding is a no-op re-write), and — with
        ``prefill_len_buckets`` — the sequence dim to the smallest
        allowed power of two covering the round's longest prompt, so
        short prompts ride short executables instead of paying
        full-``prefill_len`` prefill compute.
        """
        k = len(pending)
        with self._phase("build", "admit"):
            bucket = pow2_bucket(k)
            t = self._seq_bucket(max(len(req.tokens) for req, _ in pending))
            toks = np.zeros((bucket, t), np.int32)
            lengths = np.ones((bucket,), np.int32)
            slots = np.zeros((bucket,), np.int32)
            temps = np.zeros((bucket,), np.float32)
            wants = np.zeros((bucket,), np.int32)
            for i in range(bucket):
                req, slot = pending[min(i, k - 1)]  # pad = repeat last real
                toks[i, : len(req.tokens)] = req.tokens
                lengths[i] = max(len(req.tokens), 1)
                slots[i] = slot
                temps[i] = req.temperature
                wants[i] = req.want_left
        # ONE admission executable per (batch, length) bucket: always the
        # fused variant (the extra decode step is ~free on device, and a
        # second plain-admit executable would surprise-compile
        # mid-traffic). The paged twin reads each slot's block-table row
        # (allocated at pop time) instead of scattering into dense rows.
        t_disp = time.perf_counter()
        with self._phase("dispatch", "admit", launch=self._launches + 1), \
                self._state_lock:
            # The weights epoch this admission's prefill runs under —
            # read inside the same lock scope that passes self.params
            # to the dispatch, so it can never stamp the wrong epoch.
            for req, _slot in pending:
                req.weights_version = self.weights_version
            if self._alloc is not None:
                # Table rows go live only now, under THIS dispatch —
                # the rows' device length/active are set by the same
                # call, so no other dispatch can ever write through a
                # freshly mapped row with a stale length.
                for req, slot in pending:
                    self._set_table_row(slot, self._slot_blocks[slot])
                self._state["block_table"] = jnp.asarray(self._table)
                self._state, last, tok, emit = paged_admit_rows_and_step(
                    self._state, self.params, self.cfg,
                    jnp.asarray(slots), jnp.asarray(toks),
                    jnp.asarray(lengths), jnp.asarray(wants),
                    jnp.asarray(temps), self.top_k, self.eos_id,
                    self.kv_fused, self._kmesh)
            else:
                self._state, last, tok, emit = admit_rows_and_step(
                    self._state, self.params, self.cfg,
                    jnp.asarray(slots), jnp.asarray(toks),
                    jnp.asarray(lengths), jnp.asarray(wants),
                    jnp.asarray(temps), self.top_k, self.eos_id,
                    self._kmesh)
            launch = self._launch()
        prompt_tokens = sum(len(req.tokens) for req, _ in pending)
        self._rec.prompt_tokens += prompt_tokens
        with self._mlock:
            self.prefill_dispatches += 1
            self.admitted += k
            self.prefill_tokens += prompt_tokens
        # Fetch ONLY the fused step's tokens (one small transfer);
        # vocab-wide prefill logits stay on device behind a lazy
        # per-request resolver — an eager [K, V] fetch each admission
        # round is a copy most requests never read. The names are
        # rebound so the two device arrays are freed here, inside the
        # fetch span, as the decode round frees its own: held to the end
        # of this function they were freed after route had woken every
        # stream's reader, and the free lets go of the GIL — a
        # millisecond of this thread under no sched.* span.
        with self._phase("fetch", "admit", launch=launch):
            tok, emit = jax.device_get((tok, emit))
            self._h_dispatch.labels("admit").observe(
                time.perf_counter() - t_disp)
        with self._phase("route", "admit"):
            for i, (req, slot) in enumerate(pending):
                req.prefill_src = (last, i)
                if req.timeline is not None:
                    req.timeline.event("prefill", tokens=len(req.tokens),
                                       bucket=t)
                self._post_admit(req, slot)
            # The fused decode step's tokens (new rows' first token AND
            # every peer row's next token) — routed after _post_admit so
            # the new rows are registered.
            with self._mlock:
                self.steps += 1
            self._dispatch(tok, emit)

    def _seq_bucket(self, n: int) -> int:
        """Compiled prefill length for an ``n``-token prompt."""
        if self.prefill_len_buckets <= 0:
            return self.prefill_len
        floor = max(1, self.prefill_len >> self.prefill_len_buckets)
        return pow2_bucket(max(n, floor), cap=self.prefill_len)

    def _suffix_bucket(self, n: int) -> int:
        """Compiled suffix length for prefix-hit admissions. Suffixes are
        bucketed even when full-prompt bucketing is off — padding a
        3-token suffix to ``prefill_len`` would erase the reuse win —
        with a floor bounding the executable count."""
        if self.prefill_len_buckets > 0:
            floor = max(1, self.prefill_len >> self.prefill_len_buckets)
        else:
            floor = min(8, self.prefill_len)
        return pow2_bucket(max(n, floor), cap=self.prefill_len)

    def _plan_prefix(self, req: _Request):
        """Probe the trie for ``req`` and fit the (prefix, suffix-bucket)
        split into the cache: the suffix block must end within
        ``total_len`` (an out-of-bounds ``dynamic_update_slice`` start
        would be CLAMPED by XLA and silently corrupt the row), so when
        the bucket rounds past the prompt's tail the reused prefix is
        shortened to ``prompt_len - bucket`` — less reuse, never a wrong
        write. Returns (entry, prefix_len, bucket) with the entry pinned,
        or None (miss; any pin released)."""
        # A resumed (previously suspended) stream may consume K/V from
        # the epoch it was parked under — the payload IS its state and
        # the stream straddles the swap by design. Fresh requests must
        # only ever hit the live epoch.
        allow_stale = bool(req.out or req.folded)
        with self._prefix_lock:
            m = self.prefix_cache.match(req.tokens)
            # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; publish guard catches
            live_epoch = self.weights_version
            if (m is not None and not allow_stale
                    and m[0].version != live_epoch):
                # Stale hit: refuse, and remove the entry so it stops
                # shadowing deeper fresh entries (pinned peers keep it
                # alive until their release; it stays refused).
                entry = m[0]
                self.prefix_cache.release(entry)
                if entry.refs == 0:
                    self.prefix_cache.remove(entry)
                with self._mlock:
                    self.weight_stale_refused += 1
                m = None
        if m is None and self._host_tier is not None \
                and self._alloc is not None:
            # Second chance: a demoted (or suspended) prefix in the
            # host tier re-imports onto device and the admission
            # proceeds as an ordinary prefix hit.
            if self._promote_host_prefix(req.tokens, req.timeline,
                                         allow_stale=allow_stale):
                with self._prefix_lock:
                    m = self.prefix_cache.match(req.tokens)
        if m is None:
            return None
        entry, plen = m
        n = len(req.tokens)
        s = self._suffix_bucket(n - plen)
        if plen + s > self.total_len:
            plen = n - s
        if s >= n or plen < self.prefix_cache.min_len:
            # Too little left to reuse once bucketed — full prefill wins.
            with self._prefix_lock:
                self.prefix_cache.release(entry)
            return None
        return entry, plen, s

    def _admit_prefix(self, req: _Request, slot: int, entry,
                      prefix_len: int, s: int) -> None:
        """Prefix-hit admission: ONE dispatch gathers the cached K/V rows
        into the request's row, prefills only the suffix (padded to the
        ``s`` length bucket), and takes the fused decode step — so a
        prompt whose first ``prefix_len`` tokens are pooled pays
        suffix-sized prefill compute. ``entry`` arrives pinned
        (match() refcounted it) and stays pinned until the request
        finishes."""
        with self._phase("build", "admit"):
            suffix = req.tokens[prefix_len:]
            toks = np.zeros((1, s), np.int32)
            toks[0, : len(suffix)] = suffix
        t_disp = time.perf_counter()
        with self._phase("dispatch", "admit", launch=self._launches + 1):
            last, tok, emit = self._dispatch_prefix(
                req, slot, entry, prefix_len, toks)
            launch = self._launch()
            req.pinned_prefix = entry
            self._rec.prompt_tokens += len(suffix)
            with self._mlock:
                self.prefill_dispatches += 1
                self.admitted += 1
                self.prefix_hits += 1
                self.prefix_tokens_reused += prefix_len
                self.prefix_suffix_tokens += len(suffix)
                self.prefill_tokens += len(suffix)
        with self._phase("fetch", "admit", launch=launch):
            tok, emit = jax.device_get((tok, emit))
            self._h_dispatch.labels("admit").observe(
                time.perf_counter() - t_disp)
        with self._phase("route", "admit"):
            req.prefill_src = (last, 0)
            if req.timeline is not None:
                req.timeline.event("prefill", tokens=len(suffix),
                                   prefix_reused=prefix_len, bucket=s)
            self._post_admit(req, slot)
            with self._mlock:
                self.steps += 1
            self._dispatch(tok, emit)

    def _dispatch_prefix(self, req: _Request, slot: int, entry,
                         prefix_len: int, toks: np.ndarray):
        """Enqueue a prefix-hit admission (either KV layout). Returns
        (prefill last-logits, sampled token, emitted mask), on device."""
        if self._alloc is not None:
            # The pop-time reservation already mapped the donor's FULL
            # prefix blocks into this slot by refcount — zero device
            # copies. Here only a partially-filled tail block pays its
            # CoW (one block copy), then the suffix prefill reads the
            # shared prefix in place through the block table.
            bs = self.kv_block_size
            n_full = prefix_len // bs
            with self._state_lock:
                req.weights_version = self.weights_version
                if prefix_len % bs:
                    # First owned block (table index n_full) receives
                    # the donor's partially-shared tail content.
                    self._state["pool"] = copy_block(
                        self._state["pool"],
                        jnp.int32(self._slot_blocks[slot][n_full]),
                        jnp.int32(entry.blocks[n_full]))
                # Map the slot's table row only under its own dispatch
                # (see the pop loop: a row live before its admission is
                # a stale-length write hazard into shared blocks).
                self._set_table_row(slot, self._slot_blocks[slot])
                self._state["block_table"] = jnp.asarray(self._table)
                self._state, last, tok, emit = paged_admit_prefix_and_step(
                    self._state, self.params, self.cfg, jnp.int32(slot),
                    jnp.int32(prefix_len), jnp.asarray(toks),
                    jnp.int32(len(req.tokens)),
                    jnp.int32(req.want_left),
                    jnp.float32(req.temperature), self.top_k, self.eos_id,
                    self.kv_fused, self._kmesh)
            with self._mlock:
                self.kv_shared_blocks += n_full
                if prefix_len % bs:
                    self.kv_cow_copies += 1
        else:
            with self._prefix_lock:
                pool = self._prefix_pool
            with self._state_lock:
                req.weights_version = self.weights_version
                self._state, last, tok, emit = admit_prefix_and_step(
                    self._state, self.params, self.cfg, jnp.int32(slot),
                    pool, jnp.int32(entry.slot), jnp.int32(prefix_len),
                    jnp.asarray(toks), jnp.int32(len(req.tokens)),
                    jnp.int32(req.want_left),
                    jnp.float32(req.temperature),
                    self.top_k, self.eos_id, self._kmesh)
        return last, tok, emit

    def _begin_chunked(self, req: _Request, slot: int) -> None:
        """Register a long admission as a chunk job. The slot and its
        block reservation are taken NOW (pop time already reserved the
        blocks; a prefix plan already pinned its entry), but no device
        work runs here — the pop loop advances the chain one bounded
        chunk per round via :meth:`_advance_chunked`, interleaved with
        decode dispatches. The slot counts as OCCUPIED (no other
        admission can take it) but not ACTIVE (its row is parked;
        decode rounds don't feed it)."""
        plan = req.admit_plan
        plen = plan[1] if plan is not None else 0
        if plan is not None:
            req.pinned_prefix = plan[0]
            with self._mlock:
                self.prefix_hits += 1
                self.prefix_tokens_reused += plen
                self.prefix_suffix_tokens += len(req.tokens) - plen
                self.kv_shared_blocks += plen // self.kv_block_size
        elif self.prefix_cache is not None:
            with self._mlock:
                self.prefix_misses += 1
        req.chunk_pos = plen
        req.chunk_started = False
        self._slot_req[slot] = req
        self._chunk_jobs.append((req, slot))
        if req.timeline is not None:
            req.timeline.event("chunked_admission",
                               prompt_tokens=len(req.tokens),
                               prefix_reused=plen,
                               chunk_tokens=self.prefill_chunk_tokens)

    def _advance_chunked(self) -> bool:
        """Run AT MOST ONE chunk dispatch — the oldest job's next chunk;
        returns whether one ran.
        One chunk per round is the interleave that bounds a live
        stream's inter-token gap at one chunk of prefill compute.

        Interior chunks scatter ``prefill_chunk_tokens`` prompt tokens
        into the row's blocks and re-park the row (no sampling, no RNG
        consumed — the chain stays byte-identical to a monolithic
        prefill because K/V bytes depend only on token values and
        positions). The FINAL chunk is an ordinary prefix-style
        admission with ``prefix_len = chunk_pos``: it activates the row,
        samples the first token, and fuses the round's decode step —
        exactly the pinned prefix-hit path, so the chain ends in the
        same dispatch shape a cache hit uses."""
        if not self._chunk_jobs:
            return False
        req, slot = self._chunk_jobs[0]
        with self._phase("build", "chunk"):
            n = len(req.tokens)
            pos = req.chunk_pos
            remaining = n - pos
            final = remaining <= self.prefill_chunk_tokens
            take = remaining if final else self.prefill_chunk_tokens
            s = self._suffix_bucket(take)
            toks = np.zeros((1, s), np.int32)
            toks[0, :take] = req.tokens[pos: pos + take]
            first = not req.chunk_started
            plan = req.admit_plan
            bs = self.kv_block_size
            restart = False
        t_disp = time.perf_counter()
        with self._phase("dispatch", "chunk", launch=self._launches + 1), \
                self._state_lock:
            if first:
                # First chunk: stamp the weights epoch, CoW the plan's
                # partially-shared tail block, and map the table row —
                # all inside this dispatch's lock scope, mirroring
                # _admit_prefix (the stale-row discipline: the row
                # exists on device only once its own chain writes it).
                req.chunk_started = True
                req.weights_version = self.weights_version
                if plan is not None and plan[1] % bs:
                    n_full = plan[1] // bs
                    self._state["pool"] = copy_block(
                        self._state["pool"],
                        jnp.int32(self._slot_blocks[slot][n_full]),
                        jnp.int32(plan[0].blocks[n_full]))
                self._set_table_row(slot, self._slot_blocks[slot])
                self._state["block_table"] = jnp.asarray(self._table)
            elif req.weights_version != self.weights_version:
                # A live weight swap landed mid-chain: blocks written so
                # far are old-epoch, the rest would be new-epoch — one
                # row must never mix epochs (the trie would republish
                # the mixture). Abort below, outside the lock.
                restart = True
            if not restart:
                if final:
                    self._state, last, tok, emit = \
                        paged_admit_prefix_and_step(
                            self._state, self.params, self.cfg,
                            jnp.int32(slot), jnp.int32(pos),
                            jnp.asarray(toks), jnp.int32(n),
                            jnp.int32(req.want_left),
                            jnp.float32(req.temperature), self.top_k,
                            self.eos_id, self.kv_fused, self._kmesh,
                            ring=self._ring)
                else:
                    self._state = paged_prefill_chunk(
                        self._state, self.params, self.cfg,
                        jnp.int32(slot), jnp.int32(pos),
                        jnp.asarray(toks), jnp.int32(take),
                        self.kv_fused, self._kmesh, ring=self._ring)
                launch = self._launch()
        if restart:
            self._restart_chunked(req, slot)
            return False
        self._rec.prompt_tokens += take
        if first and plan is not None and plan[1] % bs:
            with self._mlock:
                self.kv_cow_copies += 1
        dt = time.perf_counter() - t_disp
        if not final:
            # No tokens come back: the step enqueued next waits behind
            # this chunk, and its tokens are late by it.
            self._behind = True
            req.chunk_pos = pos + take
            with self._mlock:
                self.prefill_chunks += 1
                self.prefill_tokens += take
            self._c_prefill_chunks.inc()
            self._h_prefill_chunk.observe(dt)
            self._h_dispatch.labels("prefill_chunk").observe(dt)
            if req.timeline is not None:
                req.timeline.event("prefill_chunk", pos=pos, tokens=take,
                                   bucket=s)
            return True
        # Final chunk: the chain is done — promote to an ordinary
        # admitted stream (the fused step's token dispatches below).
        self._chunk_jobs.pop(0)
        with self._mlock:
            self.prefill_dispatches += 1
            self.admitted += 1
            self.prefill_tokens += take
        with self._phase("fetch", "chunk", launch=launch):
            tok, emit = jax.device_get((tok, emit))
        self._h_dispatch.labels("admit").observe(dt)
        with self._phase("route", "chunk"):
            req.prefill_src = (last, 0)
            if req.timeline is not None:
                req.timeline.event("prefill", tokens=take,
                                   prefix_reused=pos, bucket=s,
                                   chunked=True)
            req.chunk_pos = -1
            self._post_admit(req, slot)
            with self._mlock:
                self.steps += 1
            self._dispatch(tok, emit)
        return True

    def _restart_chunked(self, req: _Request, slot: int) -> None:
        """Abort a mid-chain chunked admission and replay it from the
        queue. The whole chain restarts under the new weights epoch
        (the repop replans prefix reuse against the post-swap trie), so
        a chunked stream — like every other stream — is consistent with
        exactly one weights version, never an interleave. Swaps are
        rare relative to chain length, so the replay cost is noise and
        livelock is not a concern."""
        self._chunk_jobs.pop(0)
        self._slot_req[slot] = None
        self._release_pin(req)
        self._free_slot_blocks(slot)
        req.admit_plan = None
        req.chunk_pos = -1
        req.chunk_started = False
        if req.timeline is not None:
            req.timeline.event("chunk_restart", reason="weight_swap")
        with self._cv:
            if self._stopped:
                self._finish(req, error=RuntimeError("decoder stopped"))
                return
            self._pending.appendleft(req)
            self._cv.notify()

    def _publish_prefix(self, req: _Request, slot: int) -> None:
        """Publish a finishing request's prompt K/V (still intact in its
        row's cache positions 0..len-1) into the prefix pool, so later
        prompts sharing the prefix skip its prefill. Runs on the
        scheduler thread BEFORE the slot is freed."""
        cache = self.prefix_cache
        if cache is None or req.error is not None:
            return
        # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; swap flush removes it
        if req.weights_version != self.weights_version:
            # The stream straddled a live weight swap: its prompt K/V
            # was computed under weights the decoder no longer serves —
            # pooling it would hand stale bytes to post-swap admissions.
            return
        ent = req.pinned_prefix
        if ent is not None and getattr(ent, "version", 0) != \
                req.weights_version:
            # Plan/admit race across a swap: the prefix plan pinned a
            # then-current entry, the swap landed before the admission
            # dispatch, and the row's leading K/V is old-epoch while
            # its suffix is new. The stream itself is a legal straddler
            # (one version boundary), but its blocks must never enter
            # the trie stamped as the new epoch.
            return
        key = tuple(req.tokens)
        if len(key) < cache.min_len:
            return
        with self._prefix_lock:
            if cache.has(key):
                cache.touch(key)
                return
            entry = cache.reserve(key)
            if entry is None:  # every pool slot pinned by peers in flight
                return
            entry.version = req.weights_version
            if self._alloc is not None:
                # Paged publish is pure bookkeeping: the prompt's K/V
                # already lives in the slot's pool blocks, so the entry
                # just takes a reference on the blocks covering the key
                # (they outlive the slot's own release). ZERO copies.
                n_pub = min(self._alloc.blocks_for(len(key)),
                            len(self._slot_blocks[slot]))
                blocks = tuple(self._slot_blocks[slot][:n_pub])
                for b in blocks:
                    self._alloc.share(b)
                entry.blocks = blocks
            else:
                self._prefix_pool = store_prefix_row(
                    self._prefix_pool, jnp.int32(entry.slot),
                    # tpu-lint: disable=lock-inconsistent-guard -- dense _state scheduler-confined
                    self._state,
                    jnp.int32(slot))
            with self._mlock:
                self.prefix_inserts += 1
            plen = len(key)
            if entry.blocks:
                plen = min(plen, len(entry.blocks) * self.kv_block_size)
            self._publish_directory(key, plen, req.weights_version,
                                    tier="hbm")

    def _release_pin(self, req: _Request) -> None:
        if req.pinned_prefix is not None and self.prefix_cache is not None:
            with self._prefix_lock:
                self.prefix_cache.release(req.pinned_prefix)
            req.pinned_prefix = None

    def prime_prefix(self, tokens: list[int]) -> bool:
        """Precompute and pool a prefix (e.g. the shared system prompt at
        server start) WITHOUT touching the decode state or its RNG — a
        primed decoder samples byte-identically to an unprimed one.
        Returns True when the prefix is pooled (already or now)."""
        if self.prefix_cache is None:
            return False
        toks = list(tokens)[: self.prefill_len]
        if len(toks) < self.prefix_cache.min_len:
            return False
        key = tuple(toks)
        # One consistent (params, epoch) pair: a concurrent live weight
        # swap flips both under the state lock, and the primed entry's
        # version stamp must match the weights that computed its bytes.
        with self._state_lock:
            params, wver = self.params, self.weights_version
        with self._prefix_lock:
            if self.prefix_cache.has(key):
                self.prefix_cache.touch(key)
                return True
            entry = self.prefix_cache.reserve(key)
            if entry is None:
                return False
            if self._alloc is not None:
                # Paged prime: prefill into freshly allocated pool
                # blocks owned by the trie entry itself (refcount 1,
                # released on eviction). The state lock serializes the
                # pool write against the scheduler's donated steps.
                nblk = self._alloc.blocks_for(len(toks))
                self._reclaim_blocks(nblk)
                if not self._alloc.can_alloc(nblk):
                    self.prefix_cache.remove(entry)
                    return False
                blocks = self._alloc.alloc(nblk)
                self.kv_blocks_peak = max(self.kv_blocks_peak,
                                          self._alloc.blocks_in_use)
                try:
                    w = nblk * self.kv_block_size
                    arr = np.zeros((1, w), np.int32)
                    arr[0, : len(toks)] = toks
                    cache, _last = prefill(
                        params, jnp.asarray(arr),
                        jnp.asarray([len(toks)], np.int32), self.cfg,
                        total_len=w)
                    with self._state_lock:
                        self._state["pool"] = store_blocks(
                            self._state["pool"],
                            jnp.asarray(blocks, np.int32), cache)
                except Exception:
                    for b in blocks:
                        self._alloc.free(b)
                    self.prefix_cache.remove(entry)
                    raise
                entry.blocks = tuple(blocks)
            else:
                try:
                    t = self._seq_bucket(len(toks))
                    arr = np.zeros((1, t), np.int32)
                    arr[0, : len(toks)] = toks
                    cache, _last = prefill(
                        params, jnp.asarray(arr),
                        jnp.asarray([len(toks)], np.int32), self.cfg,
                        total_len=self.prefill_len)
                    self._prefix_pool = store_prefix_cache(
                        self._prefix_pool, jnp.int32(entry.slot), cache)
                except Exception:
                    self.prefix_cache.remove(entry)
                    raise
            entry.version = wver
            with self._mlock:
                self.prefix_inserts += 1
                self.prefill_tokens += len(toks)  # priming IS a prefill
            return True

    # -- disaggregated prefill/decode handoff --------------------------

    @staticmethod
    def _payload_nblk(payload: dict) -> int:
        """Block count a handoff payload carries (fp arrays and int8
        {"q","scale"} dicts share the [L, nblk, ...] leading layout)."""
        k = payload["k"]
        arr = k["q"] if isinstance(k, dict) else k
        return int(arr.shape[1])

    def _export_ids(self, ids: list[int]) -> dict:
        """Fetch pool blocks ``ids`` to the host as a handoff payload.
        The gather is padded to a power-of-two block count (repeating
        the last id — duplicate reads are free) so the number of
        compiled export shapes stays logarithmic, then trimmed."""
        nblk = len(ids)
        padded = ids + [ids[-1]] * (pow2_bucket(nblk) - nblk)
        # Dispatch the gather under the state lock, but fetch OUTSIDE
        # it: device_get blocks the host for the whole device→host
        # payload copy, and holding the state lock across that wait
        # would stall the scheduler's pop path for every export — the
        # same PR-9 stall class the import path already avoids. The
        # gather's result buffers are ours alone, so the fetch needs no
        # lock. (Surfaced by tpu-lint lock-blocking-call.)
        with self._state_lock:
            out_dev = export_blocks(
                self._state["pool"], jnp.asarray(padded, np.int32))
        out = jax.device_get(out_dev)

        def _trim(node):
            if isinstance(node, dict):
                return {k: v[:, :nblk] for k, v in node.items()}
            return node[:, :nblk]

        return {side: _trim(out[side]) for side in ("k", "v")}

    def _export_cold(self, prefix_toks: list[int]) -> dict:
        """Cache-less export source: prefill the prefix into scratch
        blocks, export them, free them — nothing outlives the call."""
        nblk = self._alloc.blocks_for(len(prefix_toks))
        with self._prefix_lock:
            self._reclaim_blocks(nblk)
            if not self._alloc.can_alloc(nblk):
                raise ValueError(
                    f"prompt export needs {nblk} free KV blocks; "
                    f"{self._alloc.free_blocks} available")
            blocks = self._alloc.alloc(nblk)
            self.kv_blocks_peak = max(self.kv_blocks_peak,
                                      self._alloc.blocks_in_use)
        try:
            w = nblk * self.kv_block_size
            arr = np.zeros((1, w), np.int32)
            arr[0, : len(prefix_toks)] = prefix_toks
            with self._state_lock:
                params = self.params  # consistent with any live swap
            cache, _last = prefill(
                params, jnp.asarray(arr),
                jnp.asarray([len(prefix_toks)], np.int32), self.cfg,
                total_len=w)
            with self._state_lock:
                self._state["pool"] = store_blocks(
                    self._state["pool"], jnp.asarray(blocks, np.int32),
                    cache)
            return self._export_ids(blocks)
        finally:
            with self._prefix_lock:
                for b in blocks:
                    self._alloc.free(b)

    def export_prompt(self, tokens: list[int],
                      timeout: float | None = None) -> dict:
        """Prefill-role handoff: compute the prompt's KV on THIS replica
        and export the blocks backing its leading positions as a payload
        a decode replica can :meth:`import_prompt` — the prefill half of
        disaggregated serving.

        The exported prefix is the prompt minus its last token: the
        importer re-prefills that one token through the imported blocks
        (exactly the suffix math a colocated prefix-cache hit runs), so
        its admission recovers the true last-position logits and greedy
        output stays pinned against a colocated replica. Int8 pools
        export codes AND scales verbatim — a quantized handoff is never
        re-quantized, so it is exact by construction.

        With the prefix cache on, the prefix rides the NORMAL pure-
        prefill admission (``want=0`` through the scheduler: suffix
        reuse against this replica's trie — prefix-affine routing
        concentrates shared prefixes here — queue-wait accounting,
        publish-on-finish), and the published entry's blocks are the
        export source. Without it, the prefix is prefilled into scratch
        blocks and freed after the export."""
        if self._alloc is None:
            raise ValueError("prompt handoff requires kv_layout='paged'")
        _check_hybrid(role=bool(self.cfg.mixer_types))
        toks = [int(t) for t in tokens][: self.prefill_len]
        if len(toks) < 2:
            raise ValueError("prompt handoff needs a >=2-token prompt")
        plen = len(toks) - 1
        key = tuple(toks[:plen])
        cache = self.prefix_cache
        entry = None
        if cache is not None and plen >= cache.min_len:
            with self._prefix_lock:
                known = cache.has(key)
            if not known:
                # Pure prefill through the scheduler; publish-on-finish
                # pools the prompt's blocks for the export below (and
                # for the next same-prefix export).
                self.submit(list(key), 0).result(timeout)
            with self._prefix_lock:
                m = cache.match(toks)  # pins the entry against eviction
                if m is not None:
                    entry, depth = m
                    # Cap at the positions the entry's blocks actually
                    # back (publish can cap), and keep min_len useful.
                    depth = min(depth, len(entry.blocks or ())
                                * self.kv_block_size)
                    if depth >= cache.min_len:
                        plen = depth
                    else:
                        cache.release(entry)
                        entry = None
        try:
            if entry is not None:
                ids = list(entry.blocks[: self._alloc.blocks_for(plen)])
                payload = self._export_ids(ids)
            else:
                payload = self._export_cold(toks[:plen])
        finally:
            if entry is not None:
                with self._prefix_lock:
                    cache.release(entry)
        with self._mlock:
            self.kv_handoff_exports += 1
            self.kv_handoff_tokens += plen
        # tp_shards records the exporter's mesh shape. The payload is
        # already host-global (the sharded pool gathers on device_get),
        # so a differently-sharded importer scatters it with ITS pool
        # sharding — the reshard is the import itself.
        return {"tokens": toks, "prefix_len": plen,
                "block_size": self.kv_block_size,
                "kv_dtype": self.kv_dtype, "tp_shards": self.tp_shards,
                "cp_shards": self.cp_shards, "pp_stages": self.pp_stages,
                "payload": payload}

    def import_prompt(self, handoff: dict) -> bool:
        """Decode-role handoff receive: allocate local blocks, scatter
        the exported payload in VERBATIM (int8 codes + scales included),
        and register the prefix in this replica's trie — the subsequent
        ``submit()`` of the full prompt rides the ordinary prefix-hit
        admission (full blocks refcount-shared, at most one tail CoW),
        which is pinned byte-identical to a colocated decode.

        Returns False when the import cannot be registered (no prefix
        cache, prefix under ``min_len``, every cache slot pinned, or no
        free blocks) — the caller falls back to a plain submit and this
        replica prefills the prompt itself: degraded, never wrong.
        Raises ``ValueError`` on a payload whose block size, kv dtype,
        or block count does not match this pool (importing it would
        corrupt KV)."""
        if self._alloc is None:
            raise ValueError("prompt handoff requires kv_layout='paged'")
        _check_hybrid(role=bool(self.cfg.mixer_types))
        if int(handoff["block_size"]) != self.kv_block_size:
            raise ValueError(
                f"handoff block_size {handoff['block_size']} != "
                f"pool block_size {self.kv_block_size}")
        if str(handoff.get("kv_dtype", "fp")) != self.kv_dtype:
            raise ValueError(
                f"handoff kv_dtype {handoff.get('kv_dtype')!r} != "
                f"pool kv_dtype {self.kv_dtype!r}")
        toks = [int(t) for t in handoff["tokens"]]
        plen = int(handoff["prefix_len"])
        if not 0 < plen <= min(len(toks), self.prefill_len):
            raise ValueError(f"bad handoff prefix_len {plen}")
        payload = handoff["payload"]
        cache = self.prefix_cache
        if cache is None or plen < cache.min_len:
            return False
        nblk = self._alloc.blocks_for(plen)
        if self._payload_nblk(payload) != nblk:
            raise ValueError(
                f"handoff payload carries {self._payload_nblk(payload)} "
                f"blocks; prefix_len {plen} needs {nblk}")
        imported = self._install_prefix_payload(tuple(toks[:plen]),
                                                payload)
        if imported:
            with self._mlock:
                self.kv_handoff_imports += 1
                self.kv_handoff_tokens += plen
        return imported

    def _install_prefix_payload(self, key: tuple, payload: dict, *,
                                version: int | None = None) -> bool:
        """Allocate local blocks, scatter ``payload`` in VERBATIM, and
        register ``key`` in the trie — the re-import core shared by the
        peer handoff (:meth:`import_prompt`) and host-tier promotion
        (:meth:`_promote_host_prefix`). Returns False when it cannot
        land (no free blocks, every trie slot pinned). ``version``
        stamps the installed entry's weights epoch (None = the live
        one: peer handoffs in a weight-streaming fleet are assumed
        version-aligned — the broadcast's ``max_lag`` bounds the skew)."""
        cache = self.prefix_cache
        nblk = self._alloc.blocks_for(len(key))
        if self._payload_nblk(payload) != nblk:
            raise ValueError(
                f"payload carries {self._payload_nblk(payload)} blocks; "
                f"prefix_len {len(key)} needs {nblk}")
        with self._prefix_lock:
            if cache.has(key):
                cache.touch(key)
                return True
            self._reclaim_blocks(nblk)
            if not self._alloc.can_alloc(nblk):
                return False
            blocks = self._alloc.alloc(nblk)
            self.kv_blocks_peak = max(self.kv_blocks_peak,
                                      self._alloc.blocks_in_use)
        # Device scatter OUTSIDE the prefix lock: the dispatch must
        # wait out any in-flight decode chunk (state lock), and holding
        # the prefix lock across that wait would stall the scheduler's
        # pop path — every import would freeze admissions for a chunk.
        # The blocks are ours alone until registered, so nothing reads
        # them early.
        try:
            # Same power-of-two padding as the export (duplicate
            # scatter of identical data is deterministic), so the
            # import executables stay bounded too.
            pad = pow2_bucket(nblk) - nblk
            ids = blocks + [blocks[-1]] * pad

            def _pad(node):
                if isinstance(node, dict):
                    return {k: _pad(v) for k, v in node.items()}
                if pad == 0:
                    return jnp.asarray(node)
                return jnp.asarray(np.concatenate(
                    [node] + [node[:, -1:]] * pad, axis=1))

            with self._state_lock:
                self._state["pool"] = import_blocks(
                    self._state["pool"], jnp.asarray(ids, np.int32),
                    {s: _pad(payload[s]) for s in ("k", "v")})
        except Exception:
            with self._prefix_lock:
                for b in blocks:
                    self._alloc.free(b)
            raise
        with self._prefix_lock:
            entry = cache.reserve(key)
            if entry is None:
                # A peer import won the reserve race (its blocks carry
                # identical content — the key IS the data), or every
                # cache slot is pinned. Either way our blocks are
                # surplus.
                for b in blocks:
                    self._alloc.free(b)
                imported = cache.has(key)
            else:
                entry.blocks = tuple(blocks)
                # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; swap flush re-sweeps
                entry.version = (self.weights_version
                                 if version is None else int(version))
                with self._mlock:
                    self.prefix_inserts += 1
                imported = True
        if imported:
            # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; hints validate on pull
            ver = self.weights_version if version is None else int(version)
            self._publish_directory(key, len(key), ver, tier="hbm")
        return imported

    def _promote_host_prefix(self, tokens: list[int],
                             timeline=None, *,
                             allow_stale: bool = False) -> bool:
        """Second-chance lookup: a trie miss probes the host tier for
        the longest demoted prefix of ``tokens`` and re-imports it
        through :meth:`_install_prefix_payload` — the admission then
        rides the ordinary prefix-hit path instead of a cold prefill.
        The payload stays in the tier (unpinned LRU): a later eviction
        of the promoted entry skips the re-export. ``allow_stale``
        (resumed suspended streams only) accepts payloads from an
        older weights epoch; fresh requests only match the live one."""
        with self._prefix_lock:
            # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; stale entry refused
            live_epoch = self.weights_version
            m = self._host_tier.match(
                tokens, None if allow_stale else live_epoch)
        if m is None:
            return False
        entry, depth = m
        if (self.prefix_cache is None
                or depth < self.prefix_cache.min_len):
            return False
        nblk = self._alloc.blocks_for(depth)

        def _slice(node):
            if isinstance(node, dict):
                return {k: _slice(v) for k, v in node.items()}
            return node[:, :nblk]

        # Causality: the payload's leading blocks back ANY depth <= its
        # own, so an interior match imports just the covering slice.
        payload = {s: _slice(entry.payload[s]) for s in ("k", "v")}
        if not self._install_prefix_payload(tuple(entry.key[:depth]),
                                            payload,
                                            version=entry.version):
            return False
        with self._prefix_lock:
            self._host_tier.note_promotion()
        with self._mlock:
            self.kv_host_hits += 1
        if timeline is not None:
            timeline.event("promote", prefix_len=depth)
        return True

    # -- fleet KV economy (HBM -> host -> peer -> cold) ----------------

    @staticmethod
    def _slice_payload(payload: dict, nblk: int) -> dict:
        """Covering slice of a handoff payload's leading ``nblk``
        blocks (causality: the leading blocks back any shorter depth,
        fp arrays and int8 {"q","scale"} dicts alike)."""

        def _s(node):
            if isinstance(node, dict):
                return {k: _s(v) for k, v in node.items()}
            return node[:, :nblk]

        return {side: _s(payload[side]) for side in ("k", "v")}

    def _publish_directory(self, key_tokens, prefix_len: int,
                           version: int, *, tier: str) -> None:
        """Advertise a held prefix to the fleet directory (keyed by the
        same affinity hash the gateway routes on). Cheap enough for the
        hot publish/demote paths: one leaf-locked dict write, no fleet
        round-trip — the directory stores hints and the pull validates."""
        if self.kv_directory is None:
            return
        holder = COLD_HOLDER if tier == "cold" else self.replica_name
        if not holder:
            return  # anonymous replica: nothing a peer could pull from
        key = prefix_affinity_key(key_tokens, self.kv_affinity_tokens)
        self.kv_directory.publish(key, holder,
                                  prefix_len=int(prefix_len),
                                  version=int(version), tier=tier)
        with self._mlock:
            self.kv_directory_publishes += 1

    def _demote_to_cold(self, entry) -> None:
        """Host-tier eviction hook (HostKvTier.on_evict, fired under
        the prefix lock): pack the dying payload into the shared
        content-addressed cold store and publish the hint BEFORE the
        bytes drop — the long tail demotes instead of vanishing. The
        epoch rides the content key, so a pre-swap payload parked here
        is unreachable to post-swap lookups by construction."""
        # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; stale payloads just drop
        if entry.version != self.weights_version:
            return  # stale epoch: parking it would waste cold bytes
        if self.cold_store is None or entry.prefix_len < 1:
            return
        handoff = {"tokens": list(entry.key[: entry.prefix_len]),
                   "prefix_len": int(entry.prefix_len),
                   "block_size": self.kv_block_size,
                   "kv_dtype": self.kv_dtype,
                   "tp_shards": self.tp_shards,
                   "cp_shards": self.cp_shards,
                   "pp_stages": self.pp_stages,
                   "payload": entry.payload}
        if self.cold_store.put(handoff, version=entry.version) is None:
            return
        with self._mlock:
            self.kv_cold_demotions += 1
        self._publish_directory(entry.key, entry.prefix_len,
                                entry.version, tier="cold")

    def export_prefix(self, tokens: list[int]) -> dict:
        """Serve a peer's KV pull: export the deepest cached prefix of
        ``tokens`` this replica holds — trie (device blocks, one export
        round-trip) or host tier (already host-side, free) — as a PR-9
        handoff dict stamped with the live weights epoch
        (``weights_version`` key; the requester refuses the envelope if
        its own epoch has moved on, so a mid-pull weight push degrades
        to a refusal, never to garbage KV).

        Raises ``KeyError`` when nothing matches — the directory hint
        that sent the requester here was stale; it withdraws the hint
        and falls through to the cold store or a plain prefill."""
        if self._alloc is None:
            raise ValueError("prefix export requires kv_layout='paged'")
        _check_hybrid(kv_directory=bool(self.cfg.mixer_types))
        toks = [int(t) for t in tokens]
        cache = self.prefix_cache
        entry, depth, host = None, 0, None
        with self._prefix_lock:
            # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; requester re-validates
            live = self.weights_version
            if cache is not None:
                m = cache.match(toks)  # pins against eviction
                if m is not None:
                    entry, depth = m
                    depth = min(depth, len(entry.blocks or ())
                                * self.kv_block_size)
                    if depth < cache.min_len or \
                            getattr(entry, "version", 0) != live:
                        cache.release(entry)
                        entry, depth = None, 0
            if self._host_tier is not None:
                hm = self._host_tier.match(toks, live)
                if hm is not None and hm[1] > depth:
                    host = hm
        try:
            if host is not None:
                hentry, plen = host
                payload = self._slice_payload(
                    hentry.payload, self._alloc.blocks_for(plen))
            elif entry is not None:
                plen = depth
                ids = list(entry.blocks[: self._alloc.blocks_for(plen)])
                payload = self._export_ids(ids)
            else:
                raise KeyError("no cached prefix to export")
        finally:
            if entry is not None:
                with self._prefix_lock:
                    cache.release(entry)
        with self._mlock:
            self.kv_handoff_exports += 1
            self.kv_handoff_tokens += plen
        return {"tokens": toks[:plen], "prefix_len": plen,
                "block_size": self.kv_block_size,
                "kv_dtype": self.kv_dtype, "tp_shards": self.tp_shards,
                "cp_shards": self.cp_shards, "pp_stages": self.pp_stages,
                "weights_version": live, "payload": payload}

    def _local_prefix_depth(self, toks: list[int]) -> tuple[int, int]:
        """(best local tier depth, live epoch) for the crossover check:
        the deepest of trie and host-tier match at the live weights
        epoch — anything a remote import must BEAT to be worth its
        fixed pull cost."""
        cache = self.prefix_cache
        with self._prefix_lock:
            # tpu-lint: disable=lock-inconsistent-guard -- epoch fence; install re-validates
            live = self.weights_version
            local = 0
            m = cache.match(toks)
            if m is not None:
                ent, d = m
                cache.release(ent)
                if getattr(ent, "version", 0) == live:
                    local = min(d, len(ent.blocks or ())
                                * self.kv_block_size)
            if self._host_tier is not None:
                hm = self._host_tier.match(toks, live)
                if hm is not None:
                    local = max(local, hm[1])
        return local, live

    def _maybe_import_remote(self, tokens: list[int],
                             timeline=None) -> bool:
        """The fleet miss path: trie -> host -> PEER -> COLD ->
        prefill. Runs on the CALLER thread in :meth:`submit` with no
        decoder lock held across a fetch (the pop loop plans prefixes
        under the scheduler condition — blocking I/O there would stall
        every submit; the tpu-lint lock-blocking-call fixture pair pins
        the shape). A successful import installs through
        :meth:`_install_prefix_payload`, so the pop-time plan sees an
        ordinary trie hit and prefills only the tail."""
        cache = self.prefix_cache
        if cache is None or self._alloc is None:
            return False
        if self.kv_directory is None and self.cold_store is None:
            return False
        toks = [int(t) for t in tokens]
        cap = min(len(toks) - 1, self.prefill_len)
        if cap < cache.min_len:
            return False
        local, live = self._local_prefix_depth(toks)
        # Recompute-vs-import crossover: the pull's fixed cost (RTT +
        # envelope codec + scatter dispatch) only amortizes when the
        # import saves at least this many prefill tokens over the best
        # local tier.
        want = max(cache.min_len,
                   local + max(1, self.kv_import_crossover_tokens))
        if want > cap:
            return False
        key = prefix_affinity_key(toks, self.kv_affinity_tokens)
        best_remote = 0
        if self._import_from_peers(key, toks, cap, want, live,
                                   timeline):
            return True
        if self.kv_directory is not None:
            for hint in self.kv_directory.lookup(key, version=live):
                best_remote = max(best_remote, hint.prefix_len)
        if self._import_from_cold(toks, cap, want, live, timeline):
            return True
        if self.cold_store is not None:
            best_remote = max(best_remote,
                              self.cold_store.peek_depth(toks, live))
        with self._mlock:
            if local < best_remote < want:
                self.kv_import_skipped_crossover += 1
            else:
                self.kv_peer_misses += 1
        return False

    def _import_from_peers(self, key: str, toks: list[int], cap: int,
                           want: int, live: int, timeline) -> bool:
        """Probe directory holders deepest-first; the fetch validates
        everything the hint merely promised. A dead or evicted holder
        costs one withdrawn hint, never a hang — the next holder, the
        cold store, and plain prefill are all still behind it."""
        if self.kv_directory is None or self._peer_fetch is None:
            return False
        hints = [h for h in self.kv_directory.lookup(
                     key, exclude=(self.replica_name, COLD_HOLDER),
                     version=live)
                 if h.prefix_len >= want]
        for hint in hints:
            try:
                got = self._peer_fetch(hint.holder, toks, live)
            except Exception:
                got = None
            if got is None:
                with self._mlock:
                    self.kv_peer_fetch_failures += 1
                self.kv_directory.withdraw(key, hint.holder)
                continue
            try:
                from kubeflow_tpu.serving import handoff as handoff_mod

                h = handoff_mod.unpack(got["envelope"])
                ver = int(got.get("weights_version", live))
            except (ValueError, KeyError, TypeError):
                with self._mlock:
                    self.kv_peer_fetch_failures += 1
                self.kv_directory.withdraw(key, hint.holder)
                continue
            if self._install_remote(h, ver, toks, cap, want,
                                    timeline, tier="peer"):
                return True
        return False

    def _import_from_cold(self, toks: list[int], cap: int, want: int,
                          live: int, timeline) -> bool:
        if self.cold_store is None:
            return False
        got = self.cold_store.match(toks, live)
        if got is None:
            return False
        h, depth = got
        return self._install_remote(h, live, toks, min(cap, depth),
                                    want, timeline, tier="cold")

    def _install_remote(self, h: dict, ver: int, toks: list[int],
                        cap: int, want: int, timeline,
                        tier: str) -> bool:
        """Validate a fetched envelope against THIS pool and the LIVE
        weights epoch, then install its covering slice. The epoch
        re-read is the mid-pull staleness gate: a weight push that
        landed while the envelope was in flight makes ``ver`` stale
        and the envelope is refused — counted, never installed."""
        if int(h["block_size"]) != self.kv_block_size or \
                str(h.get("kv_dtype", "fp")) != self.kv_dtype:
            with self._mlock:
                self.kv_peer_fetch_failures += 1
            return False
        with self._state_lock:
            now_live = self.weights_version
        if int(ver) != now_live:
            with self._mlock:
                self.kv_import_stale_refused += 1
            if timeline is not None:
                timeline.event("kv_import_refused", tier=tier,
                               stale_version=int(ver))
            return False
        # Actual matched depth (the hint and even the envelope's own
        # prefix_len may be optimistic — a different prompt family can
        # share an affinity key).
        ht = h["tokens"]
        lim = min(int(h["prefix_len"]), cap, len(ht))
        d = 0
        while d < lim and int(ht[d]) == toks[d]:
            d += 1
        if d < want:
            return False
        payload = self._slice_payload(h["payload"],
                                      self._alloc.blocks_for(d))
        if not self._install_prefix_payload(tuple(toks[:d]), payload,
                                            version=now_live):
            return False
        nbytes = payload_nbytes(payload)
        with self._mlock:
            if tier == "cold":
                self.kv_cold_hits += 1
                self.kv_cold_import_bytes += nbytes
            else:
                self.kv_peer_hits += 1
                self.kv_peer_import_bytes += nbytes
        if timeline is not None:
            timeline.event("kv_import", tier=tier, prefix_len=d)
        return True

    # -- live weight streaming -----------------------------------------

    def update_weights(self, params, *, version: int | None = None,
                       draft_params=None) -> int:
        """Zero-drain in-place weight swap: install a new param pytree
        between dispatches without dropping a single live stream.

        Double-buffered by construction: the new tree is placed onto
        the EXISTING shardings (tp>1 reuses shard_pytree + the model's
        partition rules, so a host-gathered push from any learner mesh
        lands correctly — the placement IS the reshard, the same trick
        as the handoff envelope) with NO lock held, while decode keeps
        dispatching against the old buffers; the install itself is a
        pointer swap under the state lock — the dispatch boundary — so
        no decode step can ever see torn weights. Live streams keep
        their slots and KV and continue across the boundary (their
        token sequences are consistent with exactly one version
        switch, never an interleave); prompt K/V cached under the old
        weights is flushed/refused so post-swap admissions are
        byte-identical to a decoder cold-started on the new weights.

        ``version`` stamps the push (monotonic; a stale or duplicate
        version is a no-op returning the installed epoch — stragglers
        in a fleet broadcast converge on the next push); None
        auto-increments. ``draft_params`` swaps a paired
        DraftModelProposer's weights in the SAME state-lock epoch —
        target and draft can never serve different versions, which
        would silently collapse speculative acceptance.

        Returns the installed weights epoch."""
        t0 = time.perf_counter()
        # One consistent (params, epoch) snapshot to validate against.
        with self._state_lock:
            cur_params, cur_version = self.params, self.weights_version
        if version is not None and int(version) <= cur_version:
            return cur_version
        # Shape/dtype contract against the serving tree (tree.map
        # raises on a structure mismatch), after the push is made one
        # (serving_params: a learner's tree has the checkpoint's names
        # and layout). A pushed leaf lands at the dtype the serving tree
        # holds it in, so a float32 learner's push installs bf16
        # matrices: a host array is cast on the host (no float32 copy
        # reaches the device), a device array on the device (the
        # float32 one stays the caller's).
        def _fit(n, o):
            if tuple(getattr(n, "shape", ())) != tuple(o.shape):
                raise ValueError(
                    f"pushed leaf shape {getattr(n, 'shape', None)} "
                    f"!= serving shape {o.shape}")
            n = np.asarray(n) if not hasattr(n, "dtype") else n
            return n.astype(o.dtype) if n.dtype != o.dtype else n

        params = jax.tree.map(_fit, serving_params(params, self.cfg),
                              cur_params)
        # Double buffer: place outside every lock. The old buffers
        # keep serving dispatches while the host→device copy streams.
        if self.mesh is not None:
            from kubeflow_tpu.models.transformer import partition_rules
            from kubeflow_tpu.parallel.sharding import shard_pytree

            new_params = shard_pytree(params, self.mesh,
                                      partition_rules(self.cfg))
        else:
            new_params = jax.device_put(params)
        jax.block_until_ready(new_params)
        spec = self._spec
        draft_new = None
        if draft_params is not None:
            if spec is None or not hasattr(spec, "params"):
                raise ValueError(
                    "draft_params given but no draft-model proposer is "
                    "configured (draft_mode='model:<name>')")
            draft_new = jax.device_put(jax.tree.map(
                _fit, serving_params(draft_params, spec.cfg), spec.params))
            jax.block_until_ready(draft_new)
        t_swap = time.perf_counter()
        with self._state_lock:
            # Re-check under the lock: a concurrent higher-versioned
            # push may have won while our buffers streamed in.
            if version is not None and int(version) <= \
                    self.weights_version:
                return self.weights_version
            self.params = new_params
            if draft_new is not None:
                spec.install_weights(draft_new)
            self.weights_version = (int(version) if version is not None
                                    else self.weights_version + 1)
            new_version = self.weights_version
        swap_s = time.perf_counter() - t_swap
        trie_flushed, tier_flushed = self._flush_stale_kv(new_version)
        total_s = time.perf_counter() - t0
        self._g_weights_version.set(new_version)
        self._g_weights_bytes.set(_weights_footprint(new_params)[0])
        self._c_weight_pushes.inc()
        self._h_weight_push.observe(total_s)
        with self._mlock:
            self.weight_pushes += 1
            # The stall decode actually pays: waiting out the in-flight
            # dispatch for the lock plus the pointer swap: at most one
            # dispatch gap by construction (not yet timed on the chip).
            self.last_swap_seconds = swap_s
        tl = self.trace.start(f"weights-v{new_version}")
        tl.event("push", version=new_version,
                 place_ms=round(1e3 * (t_swap - t0), 3),
                 draft=draft_new is not None)
        tl.event("swap", swap_ms=round(1e3 * swap_s, 3))
        if trie_flushed or tier_flushed:
            tl.event("flush", trie_entries=trie_flushed,
                     tier_entries=tier_flushed)
        tl.close()
        return new_version

    def _flush_stale_kv(self, version: int) -> tuple[int, int]:
        """Drop cached K/V computed under a pre-swap weights epoch:
        unpinned stale trie entries are removed outright (their blocks
        free; demotion is skipped — see :meth:`_demote_entry`) and
        unpinned stale host-tier payloads discarded. Entries pinned by
        in-flight admissions survive the sweep but are refused and
        removed at their next match (:meth:`_plan_prefix`); PINNED
        tier payloads are suspended streams' state and straddle the
        swap by design."""
        trie_flushed = tier_flushed = 0
        with self._prefix_lock:
            if self.prefix_cache is not None:
                for entry in self.prefix_cache.entries():
                    if entry.version != version and entry.refs == 0:
                        self.prefix_cache.remove(entry)
                        trie_flushed += 1
            if self._host_tier is not None:
                for e in self._host_tier.entries():
                    if e.version != version and not e.pinned:
                        self._host_tier.discard(e.key)
                        tier_flushed += 1
        return trie_flushed, tier_flushed

    # -- QoS: ordering, deadline shedding, stream suspension -----------

    def _order_pending_locked(self, now: float) -> None:
        """Re-order the pending deque by QoS policy (called under the
        cv): weighted fair share across tenants (tokens served over
        weight, lowest first), then priority with starvation aging,
        then FIFO — the scheduler queue's ordering applied to
        inference admission. The sort is stable, so equal keys keep
        their arrival order."""
        qos = self.qos
        with self._mlock:
            served = dict(self._tenant_served)
        self._pending = deque(sorted(
            self._pending,
            key=lambda r: order_key(
                served=served.get(r.tenant, 0.0),
                weight=qos.spec(r.tenant).weight,
                priority=r.priority,
                waited_seconds=now - r.submit_t,
                aging_seconds=qos.aging_seconds,
                submit_t=r.submit_t)))

    def _shed_expired_locked(self, now: float) -> None:
        """Shed queued requests whose deadline already passed (under
        the cv): decode compute spent on an answer nobody is waiting
        for only starves the requests that still have time."""
        expired = [r for r in self._pending
                   if r.deadline_t is not None and now > r.deadline_t]
        if not expired:
            return
        dead = {id(r) for r in expired}
        self._pending = deque(r for r in self._pending
                              if id(r) not in dead)
        with self._mlock:
            self.qos_deadline_shed += len(expired)
        for r in expired:
            if r.timeline is not None:
                r.timeline.event("deadline_shed",
                                 waited_ms=round(1e3 * (now - r.submit_t),
                                                 3))
            self._finish(r, error=DeadlineExceeded(
                f"deadline passed after {now - r.submit_t:.3f}s in queue"))

    def _pick_suspend_victim_locked(self, cand: _Request,
                                    need: int) -> int:
        """Choose a live stream to SUSPEND so the memory-blocked
        ``cand`` can admit: the lowest-base-priority stream STRICTLY
        below the candidate's base priority, whose exported KV fits
        the host tier and whose blocks actually clear the candidate's
        watermark. Base priorities on both sides deliberately: aging
        orders the QUEUE (a starved request eventually pops first) but
        must never drive preemption — an aged equal-priority candidate
        suspending a peer would ping-pong streams of one tenant
        through the host tier forever. Called with the cv AND prefix
        lock held (it reads allocator and tier state). Returns -1 when
        nothing qualifies — the round then defers exactly as before
        QoS existed."""
        if self.qos is None or self._host_tier is None:
            return -1
        victim, victim_p = -1, None
        for slot in range(self.slots):
            r = self._slot_req[slot]
            if r is None or r.want_left <= 0:
                continue
            if r.chunk_pos >= 0:
                # Mid-chain chunked admission: its row holds a partial
                # prompt that never decoded a token — there is no
                # sequence-so-far to export, only work to throw away.
                continue
            if len(r.tokens) + len(r.out) - r.folded < 2:
                continue  # a 1-token sequence has no exportable prefix
            if r.priority >= cand.priority:
                continue
            if victim_p is None or r.priority < victim_p:
                victim, victim_p = slot, r.priority
        if victim < 0:
            return -1
        r = self._slot_req[victim]
        plen = len(r.tokens) + len(r.out) - r.folded - 1
        est = (self._alloc.blocks_for(plen) * self.kv_block_size
               * self._host_bytes_per_token)
        if not self._host_tier.can_fit(est):
            return -1  # suspension must never strand an unresumable stream
        freed = len(self._slot_blocks[victim])
        if self._alloc.free_blocks + freed - need < self.kv_low_watermark:
            return -1  # even suspending wouldn't admit the candidate
        return victim

    def _suspend_stream(self, slot: int) -> None:
        """Park the live stream in ``slot``: retire its device row,
        export the KV backing its sequence-so-far into the host tier
        (PINNED — resume byte-identity depends on those exact bytes),
        free the slot and its blocks, and requeue the request. Resume
        is the ordinary pop-loop admission: the parked request's
        tokens now include everything it emitted, so it prefix-hits
        the promoted payload and continues exactly where it stopped —
        inference preemption as data-exact as the training
        scheduler's. Runs on the scheduler thread with no locks held.
        """
        req = self._slot_req[slot]
        if req is None:
            return
        seq = req.tokens + req.out[req.folded:]
        plen = len(seq) - 1
        ids = self._slot_blocks[slot][: self._alloc.blocks_for(plen)]
        # Retire the row FIRST: its blocks return to the pool below,
        # and a still-active row would scatter the next step's K/V
        # through freed (possibly re-allocated) blocks — the PR-8
        # stale-row hazard, parked the same way device-side EOS is.
        with self._state_lock:
            self._state = retire_row(self._state, slot)
        payload = self._export_ids(ids)
        key = tuple(seq[:plen])
        with self._prefix_lock:
            parked = self._host_tier.put(key, payload, plen, pinned=True,
                                         version=req.weights_version)
        self._slot_req[slot] = None
        self._active_count -= 1
        self._release_pin(req)
        self._free_slot_blocks(slot)
        if parked:
            req.host_key = key
        elif len(seq) > self.prefill_len:
            # No host copy AND too long to re-prefill cold: the stream
            # cannot resume. Unreachable while the victim pick checks
            # can_fit, but never park an unresumable request.
            self._finish(req, error=MemoryError(
                "suspended stream lost its KV payload"))
            return
        req.tokens = seq
        req.folded = len(req.out)
        req.admit_plan = None
        req.submit_t = time.perf_counter()  # queue wait re-anchors at park
        if req.timeline is not None:
            req.timeline.event("suspend", emitted=len(req.out),
                               prefix_len=plen)
        with self._mlock:
            self.kv_suspends += 1
        with self._cv:
            if self._stopped:
                self._finish(req, error=RuntimeError("decoder stopped"))
                return
            self._pending.append(req)
            self._cv.notify()

    def _mark_admitted(self, req: _Request, slot: int) -> None:
        """Record the pop→slot transition: queue-wait histogram + the
        timeline's admitted event (deferral rounds stretch this wait —
        exactly the signal the admission instrumentation must carry).
        A resumed (previously suspended) request re-anchors its wait at
        park time, so the histograms measure the park, not the whole
        stream lifetime."""
        wait = time.perf_counter() - req.submit_t
        self._h_queue_wait.observe(wait)
        self._h_tenant_wait.labels(tenant_bucket(req.tenant)).observe(wait)
        if req.out:
            # Tokens already emitted == this is a suspended stream
            # coming back; once admitted, its pinned host-tier payload
            # becomes ordinary second-chance cache.
            if req.host_key is not None and self._host_tier is not None:
                with self._prefix_lock:
                    self._host_tier.unpin(req.host_key)
                req.host_key = None
            with self._mlock:
                self.kv_resumes += 1
            if req.timeline is not None:
                req.timeline.event("resume", emitted=len(req.out),
                                   want_left=req.want_left)
        req.admit_round = req.admit_round or self._round
        if req.timeline is not None:
            req.timeline.event("admitted", slot=slot, round=self._round,
                               wait_ms=round(1e3 * wait, 3))

    def _post_admit(self, req: _Request, slot: int) -> None:
        if req.want_left == 0:
            # Pure prefill (caller wants last-position logits only): the row
            # was inserted inactive; publish its prefix, then hand the
            # result back immediately.
            self._publish_prefix(req, slot)
            self._release_pin(req)
            self._free_slot_blocks(slot)
            self._slot_req[slot] = None
            self._finish(req)
        else:
            self._slot_req[slot] = req
            self._active_count += 1
            self.peak_in_flight = max(self.peak_in_flight,
                                      self._active_count)
            if self._spec is not None:
                self._spec.reset(slot)
                self._slot_k[slot] = self.speculative_k

    def _dispatch(self, toks: np.ndarray, emitted: np.ndarray) -> None:
        """Route one step's sampled tokens ([slots]) to their requests.
        EOS parking already happened on device (``_decode_step_body``);
        the host only finishes the request and frees the slot."""
        now = time.perf_counter()
        emitted_n, gapped_n, ttft_sum, ttft_n = 0, 0, 0.0, 0
        tenant_tok: dict[str, int] = {}
        spec = self._sparse
        attended = in_context = dense = sparse = 0
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is None or not emitted[slot]:
                continue
            tok = int(toks[slot])
            req.out.append(tok)
            if spec is not None:
                # The step that emitted this token read the row with the
                # token in it: n tokens of context.
                n = len(req.tokens) + len(req.out)
                in_context += n
                if n <= spec.dense_len:
                    dense += 1
                    attended += n
                else:
                    sparse += 1
                    blocks = min(spec.topk, (n - 1) // spec.block + 1)
                    attended += ((blocks - 1) * spec.block
                                 + (n - 1) % spec.block + 1)
            tenant_tok[req.tenant] = tenant_tok.get(req.tenant, 0) + 1
            if req.ttft_s is None:
                req.ttft_s = now - req.submit_t
                ttft_sum += req.ttft_s
                ttft_n += 1
                self._h_ttft.observe(req.ttft_s)
                if req.timeline is not None:
                    req.timeline.event("first_token", round=self._round)
            elif req.last_emit_t is not None:
                self._h_itl.observe(now - req.last_emit_t)
                gapped_n += 1
            req.last_emit_t = now
            req.stream.put(tok)
            emitted_n += 1
            hit_eos = self.eos_id is not None and tok == self.eos_id
            if hit_eos or len(req.out) >= req.want:
                # Publish the finished prompt's prefix while its K/V rows
                # are still intact in the slot, then free it.
                self._publish_prefix(req, slot)
                self._release_pin(req)
                self._free_slot_blocks(slot)
                self._slot_req[slot] = None
                self._active_count -= 1
                self._finish(req, reason="eos" if hit_eos else "length")
        self._count_routed(emitted_n, gapped_n)
        with self._mlock:
            self.tokens_emitted += emitted_n
            self.sparse_tokens_attended += attended
            self.sparse_tokens_in_context += in_context
            self.rows_dense += dense
            self.rows_sparse += sparse
            self.ttft_sum += ttft_sum
            self.ttft_count += ttft_n
            for t, n in tenant_tok.items():
                self._tenant_served[t] = self._tenant_served.get(t, 0.0) + n

    def _count_routed(self, emitted_n: int, gapped_n: int) -> None:
        """Book one routed step on the round's record: ``gapped_n`` of its
        ``emitted_n`` tokens followed an earlier token of their stream,
        and came late if the route phase under way says so."""
        rec = self._rec
        rec.routed += emitted_n
        if rec.late:
            rec.routed_late += gapped_n

    def _dispatch_looped(self, toks: np.ndarray, emitted: np.ndarray) -> None:
        """:meth:`_dispatch` for a looped stack or a latent-attention model
        (bound over it at construction where ``n_passes`` > 1 or
        ``kv_lora_rank``): counts, per emitted token,
        the tokens its row held when the step that emitted it read the
        row, then routes as every model does. Plain decode steps and
        admissions' steps come through here; a verify round's tokens
        (``speculative_k``) do not, and are not counted."""
        attended = sum(
            len(req.tokens) + len(req.out) + 1
            for slot, req in enumerate(self._slot_req)
            if req is not None and emitted[slot])
        ContinuousDecoder._dispatch(self, toks, emitted)
        with self._mlock:
            self.kv_tokens_attended += attended

    def _dispatch_block(self, toks: np.ndarray, emitted: np.ndarray) -> None:
        """Route one verify step's tokens ([slots, K+1], ``emitted`` a
        per-row prefix mask) to their requests — the multi-token sibling
        of :func:`_dispatch`. The device already capped each row at its
        budget and truncated at EOS, so the mask is trusted verbatim."""
        now = time.perf_counter()
        emitted_n, gapped_n, ttft_sum, ttft_n = 0, 0, 0.0, 0
        tenant_tok: dict[str, int] = {}
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is None or not emitted[slot, 0]:
                continue
            last_tok = None
            row_emitted = 0
            for j in range(toks.shape[1]):
                if not emitted[slot, j]:
                    break
                last_tok = int(toks[slot, j])
                req.out.append(last_tok)
                if req.ttft_s is None:
                    req.ttft_s = now - req.submit_t
                    ttft_sum += req.ttft_s
                    ttft_n += 1
                    self._h_ttft.observe(req.ttft_s)
                    if req.timeline is not None:
                        req.timeline.event("first_token",
                                           round=self._round)
                req.stream.put(last_tok)
                emitted_n += 1
                row_emitted += 1
            if row_emitted:
                tenant_tok[req.tenant] = (tenant_tok.get(req.tenant, 0)
                                          + row_emitted)
                if req.last_emit_t is not None:
                    self._h_itl.observe(now - req.last_emit_t)
                    gapped_n += row_emitted
                req.last_emit_t = now
            hit_eos = self.eos_id is not None and last_tok == self.eos_id
            if hit_eos or len(req.out) >= req.want:
                self._publish_prefix(req, slot)
                self._release_pin(req)
                self._free_slot_blocks(slot)
                self._slot_req[slot] = None
                self._active_count -= 1
                self._finish(req, reason="eos" if hit_eos else "length")
        self._count_routed(emitted_n, gapped_n)
        with self._mlock:
            self.tokens_emitted += emitted_n
            self.ttft_sum += ttft_sum
            self.ttft_count += ttft_n
            for t, n in tenant_tok.items():
                self._tenant_served[t] = self._tenant_served.get(t, 0.0) + n

    def _tune_slot(self, slot: int, accepted: int, drafted: int) -> None:
        """Shrink a slot's draft length while verification keeps throwing
        its drafts away (<50% kept — the verify pass is then mostly
        wasted compute), grow it back one step per clean sweep."""
        if drafted <= 0:
            return
        if accepted * 2 < drafted:
            self._slot_k[slot] = max(1, self._slot_k[slot] - 1)
        elif accepted == drafted:
            self._slot_k[slot] = min(self.speculative_k,
                                     self._slot_k[slot] + 1)

    def _spec_round(self) -> bool:
        """One speculative decode round: collect proposals for every live
        row, verify them all in ONE fused dispatch (``chunk_size`` verify
        steps when chunking), route the accepted tokens. Returns False —
        fall through to the plain decode path — when no row has a draft
        (a verify without drafts would pay two forwards for one token).
        """
        steps, k_w = self._verify_steps, self.speculative_k
        with self._phase("build", "verify"):
            drafts, dlens = self._collect_drafts(steps, k_w)
        if not dlens.any():
            return False
        self._h_occupancy.observe(self._active_count)
        t_disp = time.perf_counter()
        with self._phase("dispatch", "verify", launch=self._launches + 1):
            with self._state_lock:
                self._state, outs, emits = verify_chunk(
                    self._state, self.params, self.cfg, jnp.asarray(drafts),
                    jnp.asarray(dlens), self.top_k, self.eos_id,
                    self.kv_fused, self._kmesh)
            launch = self._launch()
            with self._mlock:
                self.dispatches += 1
                self.spec_verify_dispatches += 1
                self.steps += 2 * steps  # scoring + commit per verify
        self._ramp_streak = 0
        with self._phase("fetch", "verify", launch=launch):
            outs, emits = jax.device_get((outs, emits))
            self._h_dispatch.labels("verify").observe(
                time.perf_counter() - t_disp)
        with self._phase("route", "verify"):
            for s in range(steps):
                # Accounting before routing: routing may free the slot.
                drafted, accepted = 0, 0
                for slot in range(self.slots):
                    d = int(dlens[s, slot])
                    if d == 0 or self._slot_req[slot] is None:
                        continue
                    m = int(emits[s, slot].sum())
                    acc = min(max(m - 1, 0), d)
                    drafted += d
                    accepted += acc
                    if m:
                        self._tune_slot(slot, acc, d)
                with self._mlock:
                    self.spec_drafted_tokens += drafted
                    self.spec_accepted_tokens += accepted
                self._dispatch_block(outs[s], emits[s])
        return True

    def _collect_drafts(self, steps: int, k_w: int):
        """Proposals for every live row as the verify dispatch takes
        them: (drafts [steps, slots, k_w], their lengths [steps, slots])."""
        asks = []
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is not None:
                # steps-1 extra chain tokens: each verify step's commit
                # consumes one, so the next step's slice starts after it.
                asks.append((slot, req.tokens + req.out,
                             steps * self._slot_k[slot] + steps - 1))
        drafted = self._spec.dispatches
        props = self._spec.propose(asks)
        for _ in range(self._spec.dispatches - drafted):
            self._launch()   # a draft model's dispatch; n-grams make none
        drafts = np.zeros((steps, self.slots, k_w), np.int32)
        dlens = np.zeros((steps, self.slots), np.int32)
        for slot, ctx, _n in asks:
            prop = props.get(slot) or []
            req = self._slot_req[slot]
            budget = req.want - len(req.out)  # tokens the row may still emit
            off = 0
            for s in range(steps):
                if budget <= 0:
                    break
                # A verify step emits dlen accepted drafts + 1 commit:
                # cap dlen so a near-done row doesn't drown its
                # acceptance stats (and the verify pass) in drafts the
                # budget could never emit.
                k_use = min(self._slot_k[slot], budget - 1)
                seg = prop[off: off + k_use]
                # Skip the token the commit pass emits between slices —
                # under full acceptance it IS the next chain token, so
                # without the skip every later slice arrives off-by-one.
                off += len(seg) + 1
                budget -= len(seg) + 1
                if not seg:
                    break
                drafts[s, slot, : len(seg)] = seg
                dlens[s, slot] = len(seg)
        return drafts, dlens

    def _loop(self) -> None:
        """Scheduler-thread entry: run the loop, and on ANY exit — clean
        stop, inner-handler return, or an escaped exception — fail every
        stream still live so no StreamHandle ever hangs out its timeout
        waiting on a dead loop."""
        err: Exception = RuntimeError("decoder stopped")
        try:
            self._run()
        except Exception as e:
            err = e
        finally:
            self._fail_all(err)

    def _fail_all(self, err: Exception) -> None:
        with self._cv:
            self._stopped = True
            queued = list(self._pending)
            self._pending.clear()
        self._chunk_jobs.clear()
        self._inflight = None
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is not None:
                self._slot_req[slot] = None
                # Mid-chain chunked admissions occupy a slot without
                # counting as active (their row is parked, not decoding).
                if req.chunk_pos < 0:
                    self._active_count -= 1
                self._finish(req, error=err)
            # Every slot's block references return to the pool — also
            # covers blocks reserved at pop time for an admission that
            # never registered (idempotent with the finish path's free).
            self._free_slot_blocks(slot)
        for req in queued:
            self._finish(req, error=err)

    @contextlib.contextmanager
    def _phase(self, phase: str, kind: str, **args):
        """One phase of the current scheduler round: a ``sched.<phase>``
        span on the profiler's clock (a no-op unless a capture is open;
        ``args`` become its arguments beside ``round`` and ``kind``) and,
        always, its wall seconds on the round's record."""
        rec = self._rec
        if phase == "route":
            rec.late = kind != "decode"
        t0 = time.perf_counter()
        with host_span(SPAN_PREFIX + phase, round=self._round, kind=kind,
                       **args):
            yield
        rec.phase_s[phase] += time.perf_counter() - t0

    def _launch(self) -> int:
        """Count the step dispatch just enqueued; its ordinal."""
        self._launches += 1
        self._rec.launched(self._launches)
        return self._launches

    def _run(self) -> None:
        clocks = time.perf_counter(), time.time(), time.thread_time()
        while True:
            self._round += 1
            rec = self._rec = RoundRecord(
                self._round, self._active_count, *clocks)
            with host_span(SPAN_ROUND, round=rec.round,
                           active=rec.active) as span:
                done = self._run_round()
                if done is None:
                    return
                # The next round starts here: rounds tile the thread's
                # time, and what follows (the log line of a slow round
                # with it) is under no phase of the next one. ONE read of
                # the thread's CPU clock a round: it is a system call, 6
                # us on a v5e host, where the other two are not.
                clocks = time.perf_counter(), time.time(), time.thread_time()
                rec.close(*done, clocks[0], clocks[2])
                self.rounds.add(rec)
                if span.is_enabled():
                    span.set_metadata(**rec.span_metadata())
            for phase, seconds in rec.phase_s.items():
                if seconds:
                    self._c_phase[phase].inc(seconds)
            if rec.slow:
                self.rounds.report(rec)

    def _plan_round(self):
        """Wait for work, then plan the round under the cv: shed, order,
        pop, reserve blocks. Returns (popped ``(request, slot)`` pairs,
        slot of a stream to suspend or -1, whether the loop idled), or
        None once stopped."""
        idled = False
        with self._cv:
            if (not self._stopped and not self._pending
                    and self._active_count == 0 and not self._chunk_jobs):
                idled = True
                with self._phase("idle", "idle"):
                    while (not self._stopped and not self._pending
                           and self._active_count == 0
                           and not self._chunk_jobs):
                        self._cv.wait(timeout=0.5)
        # The wait for the queue lock (submits hold it) is plan's too.
        with self._phase("plan", "admit"), self._cv:
            if self._stopped:
                return None
            pending, suspend_slot = self._plan_admissions_locked()
        return pending, suspend_slot, idled

    def _plan_admissions_locked(self):
        """The admission plan of one round (called under the cv)."""
        now = time.perf_counter()
        self._shed_expired_locked(now)
        if self.qos is not None and len(self._pending) > 1:
            self._order_pending_locked(now)
        pending = []
        deferred = False
        suspend_slot = -1
        free_slots = [s for s in range(self.slots)
                      if self._slot_req[s] is None]
        if self._alloc is None:
            while free_slots and self._pending:
                req = self._pending.popleft()
                slot = free_slots.pop(0)
                self._mark_admitted(req, slot)
                pending.append((req, slot))
        else:
            # Memory-aware admission: a request enters only when
            # its WORST-CASE block count fits the pool (so the
            # stream can never OOM mid-decode), reserving the
            # blocks here so prime_prefix can't race them away.
            # The prefix plan runs FIRST: a hit pins its entry
            # (reclaim then can't evict it underneath) and
            # shrinks the reservation to the non-shared blocks.
            # The low-watermark defers admission while other
            # work is in flight instead of draining the pool to
            # zero headroom. Three QoS/fairness extensions ride
            # on top: candidates arrive in fair-share/priority
            # order; a memory-blocked head may be BYPASSED by
            # up to hol_bypass_limit later candidates that fit
            # (defer_rounds aging shields it from starving);
            # and when the blocked candidate outranks a live
            # stream, that stream is SUSPENDED to the host tier
            # instead of the whole queue deferring.
            idx = 0
            bypassed = 0
            while free_slots and idx < len(self._pending):
                req = self._pending[idx]
                worst = self._alloc.blocks_for(
                    max(len(req.tokens), 1) + req.want_left)
                # TERMINAL size rejections (vs. the silent defer
                # memory pressure takes): the request could
                # never be served no matter how long it waits —
                # either its worst-case block count exceeds the
                # whole pool, or its tokens + budget overflow
                # the virtual row. PromptTooLong -> HTTP 413.
                if (worst > self._alloc.num_blocks
                        or len(req.tokens) + req.want_left
                        > self.total_len):
                    del self._pending[idx]
                    with self._mlock:
                        self.prompt_rejected_too_long += 1
                    self._finish(req, error=PromptTooLong(
                        f"request needs {worst} KV blocks "
                        f"({len(req.tokens)} prompt + "
                        f"{req.want_left} new tokens) but the "
                        f"pool holds {self._alloc.num_blocks} "
                        f"blocks / {self.total_len} tokens"))
                    continue
                plan = (self._plan_prefix(req)
                        if self.prefix_cache is not None else None)
                n_shared = (plan[1] // self.kv_block_size
                            if plan is not None else 0)
                need = worst - n_shared
                fits = True
                # A parked stream longer than the compiled
                # prompt shape can only resume through its
                # exported prefix — without a plan it waits for
                # the promote to find memory, never cold-
                # prefills a truncated sequence.
                # (Chunked prefill lifts the cold ceiling: any
                # in-row-bounds sequence can re-prefill as a
                # chain of chunks, plan or no plan.)
                resumable = (plan is not None
                             or self.prefill_chunk_tokens > 0
                             or len(req.tokens) <= self.prefill_len)
                with self._prefix_lock:
                    self._reclaim_blocks(need, req.timeline)
                    headroom = self._alloc.free_blocks - need
                    busy = self._active_count > 0 or pending
                    if (not resumable
                            or headroom < (self.kv_low_watermark
                                           if busy else 0)):
                        fits = False
                        if plan is not None:
                            self.prefix_cache.release(plan[0])
                        if not deferred:
                            deferred = True
                            suspend_slot = \
                                self._pick_suspend_victim_locked(
                                    req, need)
                    else:
                        own = self._alloc.alloc(need)
                        shared = (list(plan[0].blocks[:n_shared])
                                  if plan is not None else [])
                        for b in shared:
                            self._alloc.share(b)
                        self.kv_blocks_peak = max(
                            self.kv_blocks_peak,
                            self._alloc.blocks_in_use)
                if fits:
                    req.admit_plan = plan
                    req.defer_rounds = 0
                    slot = free_slots.pop(0)
                    self._slot_blocks[slot] = shared + own
                    # The TABLE row stays sentinel until this
                    # request's own admission dispatch uploads
                    # it (_admit_prefix/_admit_batch). Pointing
                    # it at the blocks now would arm a
                    # stale-row write: an earlier admission's
                    # fused decode step in the SAME round still
                    # sees this slot's old device length, and
                    # its unconditional K/V scatter would land
                    # junk inside these blocks — including
                    # refcount-SHARED prefix blocks other
                    # streams read.
                    del self._pending[idx]
                    if bypassed:
                        with self._mlock:
                            self.hol_bypasses += 1
                    self._mark_admitted(req, slot)
                    pending.append((req, slot))
                    continue
                # Blocked: note the deferral, but keep scanning
                # for a smaller candidate that fits — unless
                # this head has aged past the bypass shield
                # (then nothing younger may jump it again).
                req.defer_rounds += 1
                if req.timeline is not None:
                    req.timeline.event(
                        "deferred", need=need,
                        free=self._alloc.free_blocks)
                if req.defer_rounds >= self.hol_shield_rounds:
                    break
                bypassed += 1
                if bypassed > self.hol_bypass_limit:
                    break
                idx += 1
        if deferred:
            with self._mlock:
                self.kv_defer_admissions += 1
        return pending, suspend_slot

    def _run_round(self):
        """One pass of the scheduler loop. Returns (the kind of its last
        dispatch — admit, chunk, verify or decode —, requests admitted),
        or None once stopped."""
        planned = self._plan_round()
        if planned is None:
            return None
        pending, suspend_slot, idled = planned
        if idled:
            # Coming out of idle: the streak cap must not outlive
            # the burst that set it — the next admission deserves
            # its ramp round. Reset OUTSIDE the cv so every
            # _ramp_streak access stays scheduler-thread-plain
            # (one site under the cv made the guard inconsistent).
            self._ramp_streak = 0
        try:
            return self._dispatch_round(pending, suspend_slot), len(pending)
        except Exception as e:
            # A failed prefill/decode/verify may have invalidated
            # self._state (the jitted calls donate its buffers), so
            # the decoder cannot safely take more work. Requests
            # popped this round but not yet registered in a slot
            # would be invisible to the loop-exit sweep — fail them
            # here (returning any pop-time block reservation), then
            # let _loop's wrapper fail everything else (in-flight
            # and queued) with the same error.
            for req, _slot in pending:
                self._finish(req, error=e)
                self._free_slot_blocks(_slot)
            raise

    def _dispatch_round(self, pending, suspend_slot) -> str:
        """The device half of a round: admissions, one chunk of a long
        admission, then a verify or decode step. Returns the kind of the
        last dispatch."""
        if self._inflight is not None and (
                suspend_slot >= 0 or pending or self._chunk_jobs):
            # Everything but a plain decode step routes tokens of its
            # own: the step in flight delivers its tokens first.
            self._deliver_inflight()
        if suspend_slot >= 0:
            # Preempt-to-host: the victim was chosen under the
            # cv, but the export is a device round-trip submits
            # must not wait on — executed here, outside the cv.
            # Its freed blocks admit the blocked candidate on
            # the next round.
            self._suspend_stream(suspend_slot)
            self._behind = True
        if pending:
            # Admission fuses prefill + insert + one decode step
            # into a single dispatch, so a new request's first
            # token ships on the admission dispatch itself
            # (prompt→token = one dispatch). Whether the round
            # ALSO runs its chunk is the TTFT-ramp streak cap:
            # normally an admission round ends here (fast first
            # token, next round chunks), but under sustained
            # arrivals (pending non-empty nearly every round) at
            # most one consecutive admission-only round is
            # allowed before a fused chunk runs in the same
            # round — decode throughput must not degrade toward
            # one dispatch per token. (want==0 admissions are
            # pure prefills answered in _post_admit.)
            #
            # With the prefix cache on, each request first probes
            # the trie: hits ride suffix-only admissions (one
            # dispatch each), misses batch as before.
            if self.prefill_chunk_tokens:
                # Long admissions (suffix wider than one chunk)
                # leave the one-dispatch paths: they register as
                # chunk jobs and the pop loop feeds them one
                # bounded chunk per round, interleaved with
                # decode — a 32k admission no longer stalls
                # every live stream for a monolithic prefill.
                short = []
                for req, slot in pending:
                    plan = req.admit_plan
                    plen = plan[1] if plan is not None else 0
                    if (len(req.tokens) - plen
                            > self.prefill_chunk_tokens):
                        self._begin_chunked(req, slot)
                    else:
                        short.append((req, slot))
                pending = short
            misses = pending
            if self.prefix_cache is not None:
                hits, misses = [], []
                for req, slot in pending:
                    # Paged admissions planned at pop time (the
                    # plan gates the block reservation); dense
                    # ones probe the trie here.
                    plan = (req.admit_plan
                            if self._alloc is not None
                            else self._plan_prefix(req))
                    if plan is None:
                        with self._mlock:
                            self.prefix_misses += 1
                        misses.append((req, slot))
                    else:
                        hits.append((req, slot, plan))
                for req, slot, (entry, plen, s) in hits:
                    self._admit_prefix(req, slot, entry, plen, s)
            # As many rows a dispatch as the decode layer takes.
            rows = self._admit_rows or max(1, len(misses))
            for i in range(0, len(misses), rows):
                self._admit_batch(misses[i:i + rows])
            ramp = (any(req.want_left for req, _ in pending)
                    and (self.chunk_size == 1
                         or self._ramp_streak < 1))
            if ramp:
                if self.chunk_size > 1:
                    self._ramp_streak += 1
                # A ramp round still owes the oldest chunked
                # admission its chunk — TTFT ramping must not
                # starve a long prefill chain.
                self._advance_chunked()
                return "admit"  # this round's step already ran
        chunked = self._advance_chunked()
        if self._active_count == 0:
            return "chunk" if chunked else "admit"
        if self._spec is not None and self._spec_round():
            return "verify"
        with self._phase("dispatch", "decode", launch=self._launches + 1):
            self._h_occupancy.observe(self._active_count)
            t_disp = time.perf_counter()
            with self._state_lock:
                if self.chunk_size > 1:
                    self._state, toks, emitted = decode_chunk(
                        self._state, self.params, self.cfg,
                        self.chunk_size, self.top_k, self.eos_id,
                        self.kv_fused, self._kmesh,
                    )
                else:
                    self._state, toks, emitted = decode_step(
                        self._state, self.params, self.cfg,
                        self.top_k, self.eos_id, self.kv_fused,
                        self._kmesh,
                    )
            launch = self._launch()
            late, self._behind = self._behind, False
            with self._mlock:
                self.steps += self.chunk_size
                self.dispatches += 1
        # One step ahead. The device state holds all the next step needs
        # (lengths, budgets, EOS parking), so this step was enqueued
        # before the last one's tokens are fetched and routed: the host's
        # part of a round runs beside the device's, not between two
        # steps. A finish is seen a step late; that step emits nothing
        # for the row, which the device has parked.
        last, self._inflight = self._inflight, (toks, emitted, t_disp,
                                                launch, late)
        if last is not None:
            self._deliver(*last)
        if self._active_count == 0 or self._spec is not None:
            # Nothing left to run ahead of (or the proposer reads every
            # token a row has): take this step's tokens now.
            self._deliver_inflight()
        return "decode"

    def _deliver(self, toks, emitted, t_disp: float, launch: int,
                 late: bool) -> None:
        """Fetch one plain decode dispatch's tokens and route them.
        ``launch`` is the dispatch's ordinal; ``late``, whether it was
        enqueued behind a chunk or a suspension."""
        with self._phase("fetch", "decode", launch=launch):
            toks, emitted = jax.device_get((toks, emitted))
            self._h_dispatch.labels("decode").observe(
                time.perf_counter() - t_disp)
        with self._phase("route", "decode"):
            self._rec.late = late
            if self.chunk_size > 1:
                self._ramp_streak = 0
                for k in range(self.chunk_size):
                    self._dispatch(toks[k], emitted[k])
            else:
                self._dispatch(toks, emitted)

    def _deliver_inflight(self) -> None:
        step, self._inflight = self._inflight, None
        if step is not None:
            self._deliver(*step)

    # ------------------------------------------------------------------

    def metrics(self) -> dict:
        cache = self.prefix_cache
        # Queue depth is cv-guarded state: snapshot it under the cv in
        # its own scope (never nested with the metrics lock).
        with self._cv:
            queued = len(self._pending)
        # One lock-guarded snapshot of every counter the scheduler
        # mutates, so derived ratios (ttft_avg_s, spec_acceptance_rate)
        # are computed from matching sum/count pairs — never from a
        # torn read taken mid-update.
        with self._mlock:
            snap = {
                "decode_steps": self.steps,
                "decode_dispatches": self.dispatches,
                "prefill_dispatches": self.prefill_dispatches,
                "prefill_tokens": self.prefill_tokens,
                "prefill_chunks": self.prefill_chunks,
                "prompt_rejected_too_long": self.prompt_rejected_too_long,
                "max_prompt_len": self.max_prompt_len,
                "prefill_chunk_tokens": self.prefill_chunk_tokens,
                "requests_admitted": self.admitted,
                "tokens_emitted": self.tokens_emitted,
                "ttft_avg_s": (self.ttft_sum / self.ttft_count
                               if self.ttft_count else 0.0),
                "in_flight": self._active_count,
                "peak_in_flight": self.peak_in_flight,
                "queued": queued,
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_tokens_reused": self.prefix_tokens_reused,
                "prefix_suffix_tokens": self.prefix_suffix_tokens,
                "prefix_inserts": self.prefix_inserts,
                "spec_drafted_tokens": self.spec_drafted_tokens,
                "spec_accepted_tokens": self.spec_accepted_tokens,
                "spec_verify_dispatches": self.spec_verify_dispatches,
                "spec_draft_dispatches": (self._spec.dispatches
                                          if self._spec is not None else 0),
                "spec_acceptance_rate": (
                    self.spec_accepted_tokens / self.spec_drafted_tokens
                    if self.spec_drafted_tokens else 0.0),
                "spec_draft_k": (sum(self._slot_k) / len(self._slot_k)
                                 if self._slot_k else 0.0),
                "state_bytes": self.state_bytes,
                "sparse_attn_impl": self.sparse_attn_impl,
                "dense_attn_impl": self.dense_attn_impl,
                "sparse_tokens_attended": self.sparse_tokens_attended,
                "sparse_tokens_in_context": self.sparse_tokens_in_context,
                "rows_dense": self.rows_dense,
                "rows_sparse": self.rows_sparse,
                "cache_layers": self.cfg.cache_layers,
                "loop_passes": self.steps * self.cfg.n_passes,
                "kv_tokens_attended": self.kv_tokens_attended,
                "kv_cow_copies": self.kv_cow_copies,
                "kv_shared_blocks": self.kv_shared_blocks,
                "kv_defer_admissions": self.kv_defer_admissions,
                "kv_handoff_exports": self.kv_handoff_exports,
                "kv_handoff_imports": self.kv_handoff_imports,
                "kv_handoff_tokens": self.kv_handoff_tokens,
                "kv_suspends": self.kv_suspends,
                "kv_resumes": self.kv_resumes,
                "kv_host_hits": self.kv_host_hits,
                "kv_peer_hits": self.kv_peer_hits,
                "kv_peer_misses": self.kv_peer_misses,
                "kv_peer_import_bytes": self.kv_peer_import_bytes,
                "kv_peer_fetch_failures": self.kv_peer_fetch_failures,
                "kv_cold_hits": self.kv_cold_hits,
                "kv_cold_demotions": self.kv_cold_demotions,
                "kv_cold_import_bytes": self.kv_cold_import_bytes,
                "kv_import_stale_refused": self.kv_import_stale_refused,
                "kv_import_skipped_crossover":
                    self.kv_import_skipped_crossover,
                "kv_directory_publishes": self.kv_directory_publishes,
                "qos_deadline_shed": self.qos_deadline_shed,
                "hol_bypasses": self.hol_bypasses,
                "qos_enabled": self.qos is not None,
                "tenant_served": dict(self._tenant_served),
                "role": self.role,
                "tp_shards": self.tp_shards,
                "cp_shards": self.cp_shards,
                "pp_stages": self.pp_stages,
                "weight_pushes": self.weight_pushes,
                "weights_stale_refused": self.weight_stale_refused,
                "weight_swap_seconds_last": self.last_swap_seconds,
                "compile_cache_hits": self.compile_cache_hits,
                "compile_cache_misses": self.compile_cache_misses,
                "warm_failed_shapes": self.warm_failed_shapes,
                "warm_seconds": self.warm_seconds,
                "warming": self.warming,
                "slow_rounds": self.rounds.slow_rounds,
                "slow_round_seconds": self.rounds.slow_seconds,
            }
        # The weights epoch swaps under the state lock; its own scope
        # (never nested with the other snapshot locks) keeps the read
        # consistent without coupling the lock hierarchies.
        with self._state_lock:
            snap["weights_version"] = self.weights_version
            snap["qkv_held_transposed"] = self.qkv_held_transposed
            snap["weights_bytes"], snap["weights_dtype"] = \
                _weights_footprint(self.params)
            counts = None
            if self.cfg.kv_lora_rank and self._state is not None:
                # The device's own counts (the dropless expert layer),
                # copied on the device behind the step in flight: the next
                # step donates the state, its copy outlives it.
                counts = (jnp.copy(self._state["moe_routed"]),
                          jnp.copy(self._state["moe_touched"]))
        if counts is not None:
            # Fetched here and only here (no round asks), outside the lock.
            routed, touched = jax.device_get(counts)
            snap["moe_routed"] = routed.tolist()
            snap["moe_touched"] = touched.tolist()
        # Allocator / trie stats live under the prefix lock — taken in a
        # SEPARATE scope (never nested with the metrics lock) so the two
        # subsystems can't deadlock against each other.
        with self._prefix_lock:
            snap["prefix_evictions"] = cache.evictions if cache else 0
            snap["prefix_entries"] = len(cache) if cache else 0
            snap["kv_blocks_total"] = (self._alloc.num_blocks
                                       if self._alloc else 0)
            snap["kv_blocks_in_use"] = (self._alloc.blocks_in_use
                                        if self._alloc else 0)
            snap["kv_blocks_peak"] = self.kv_blocks_peak
            snap["kv_block_size"] = (self.kv_block_size
                                     if self._alloc else 0)
            # Real-byte accounting: the autoscaler must scale on bytes
            # resident, not block counts whose HBM meaning shifts with
            # kv_dtype (an int8 block is ~half an fp block).
            snap["kv_dtype"] = self.kv_dtype if self._alloc else "fp"
            snap["kv_fused"] = self.kv_fused
            snap["kv_bytes_per_token"] = self.kv_bytes_per_token
            snap["kv_bytes_in_use"] = (self._alloc.bytes_in_use
                                       if self._alloc else 0)
            snap["kv_bytes_total"] = (self._alloc.bytes_total
                                      if self._alloc else 0)
            # Host-tier (HBM -> host) occupancy: the second-chance
            # cache plus pinned suspended-stream payloads. Pinned bytes
            # draining to zero is the suspension leak invariant.
            tier = self._host_tier
            snap["kv_host_tier_bytes"] = tier.bytes_in_use if tier else 0
            snap["kv_host_tier_bytes_total"] = (tier.capacity_bytes
                                                if tier else 0)
            snap["kv_host_tier_pinned_bytes"] = (tier.pinned_bytes
                                                 if tier else 0)
            snap["kv_host_tier_entries"] = len(tier) if tier else 0
            snap["kv_host_demotions"] = tier.demotions if tier else 0
            snap["kv_host_evictions"] = tier.evictions if tier else 0
            snap["kv_host_promotions"] = tier.promotions if tier else 0
            snap["kv_host_tier_high_water_bytes"] = (
                tier.high_water_bytes if tier else 0)
        # Shared-tier stats carry their own leaf locks (the directory
        # and cold store are fleet-shared objects — other replicas'
        # submit probes touch them concurrently with this snapshot).
        if self.cold_store is not None:
            cold = self.cold_store.stats()
            snap["kv_cold_store_bytes"] = cold["bytes_in_use"]
            snap["kv_cold_store_bytes_total"] = cold["capacity_bytes"]
            snap["kv_cold_store_entries"] = cold["entries"]
            snap["kv_cold_store_evictions"] = cold["evictions"]
        if self.kv_directory is not None:
            snap["kv_directory_keys"] = self.kv_directory.stats()["keys"]
        # Histogram-backed latency quantiles (ttft_avg_s above stays for
        # backward compatibility — dashboards and test_observability read
        # it — but the distribution is what autoscaling policies need).
        # Histogram locks are leaves, taken outside the snapshot locks.
        for key, hist in (("ttft", self._h_ttft),
                          ("inter_token", self._h_itl),
                          ("queue_wait", self._h_queue_wait)):
            for q, tag in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
                snap[f"{key}_{tag}_s"] = hist.quantile(q)
        return snap
