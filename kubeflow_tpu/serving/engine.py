"""Inference engine: registry model + checkpoint → jitted predict.

TPU-first: one compiled function per (padded) batch shape, inputs padded to
the fixed server batch so every request rides the same executable; bf16
activations; optional greedy decode loop for LMs via ``lax.scan`` (static
length, compiled once).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.registry import ModelSpec, get_model


def pow2_bucket(n: int, cap: int | None = None) -> int:
    """Smallest power of two >= ``n`` (floored at 1), clamped to ``cap``.

    The shared shape-bucketing rule: the continuous decoder buckets BOTH
    its admission batch size and (with ``prefill_len_buckets``) the
    prefill sequence length through this, so the number of compiled
    prefill executables stays logarithmic in each dimension.
    """
    bucket = 1
    while bucket < n:
        bucket *= 2
    if cap is not None:
        bucket = min(bucket, cap)
    return bucket


@dataclass
class EngineConfig:
    model: str = "lm-test-tiny"
    checkpoint_dir: str | None = None
    batch_size: int = 8
    max_seq_len: int = 128
    # Autoregressive decode surface (transformer family): fixed compiled
    # decode length (instances request up to this many), optional top-k.
    max_new_tokens: int = 16
    top_k: int = 0
    # Sampling this id ends a generation early (frees the decode slot);
    # None disables (the synthetic test models have no EOS convention).
    eos_id: int | None = None
    # "continuous": per-request lengths decoupled, streamable (default).
    # "lockstep": one compiled prefill+decode per batch — one dispatch
    # for the whole generation; every row runs the full compiled length
    # and nothing streams. Which survives is ROADMAP D4's to decide.
    decode_mode: str = "continuous"
    # Decode steps fused into one device dispatch in continuous mode
    # (models/decode.py:decode_chunk). 1 = one dispatch per token (the
    # finest streaming/admission granularity). K>1 makes K× fewer
    # dispatches; a new request is admitted, and a token streamed, only
    # at a chunk boundary, so each waits up to K steps. Per-request
    # decoupling is kept either way. The value is ROADMAP D4's to decide.
    decode_chunk: int = 1
    # Device-resident prefix KV cache (continuous mode): pool slots for
    # cached prompt prefixes (0 disables). A matching admission gathers
    # the cached K/V rows and prefills ONLY its suffix; finished prompts
    # publish their prefix back to the pool (LRU eviction, in-flight
    # pins). Memory per slot: 2 * layers * max_seq_len * kv_heads *
    # head_dim * dtype bytes.
    prefix_cache_slots: int = 0
    # Shortest prefix worth caching or matching: below this the reuse
    # bookkeeping costs more than the prefill it saves.
    prefix_cache_min_len: int = 16
    # Speculative decoding (continuous mode): number of draft tokens
    # verified per fused dispatch (0 disables). A verify scores K cheap
    # proposals in one [slots, K] forward and keeps each row's longest
    # accepted prefix plus one committed token — up to K+1 tokens per
    # decode round-trip. Greedy outputs are byte-identical either way;
    # temperature>0 rows rejection-resample (distribution unchanged).
    speculative_k: int = 0
    # Where drafts come from: "ngram" (host-side prompt/output n-gram
    # lookup, zero device cost) or "model:<registry-name>" (a small
    # draft model sharing the slot layout).
    draft_mode: str = "ngram"
    # Power-of-two sequence-length buckets for prefill: the number of
    # bucket steps below max_seq_len (0 = pad every prompt to
    # max_seq_len). E.g. 3 with max_seq_len=128 allows prefill shapes
    # {16, 32, 64, 128}, so a 6-token prompt rides a 16-wide executable
    # instead of paying full-length prefill compute.
    prefill_len_buckets: int = 0
    # KV-cache layout (continuous mode). "dense": one
    # [total_len = max_seq_len + max_new_tokens] K/V row reserved per
    # decode slot — worst-case HBM per admission. "paged": K/V lives in
    # a pool of kv_block_size-token blocks mapped through per-slot block
    # tables, so a request only holds blocks for its OWN prompt+budget,
    # admission is bounded by memory (tokens resident) instead of slots,
    # and prefix-cache hits share blocks by refcount with zero device
    # copies. Greedy outputs are byte-identical between layouts.
    kv_layout: str = "dense"
    # Tokens per KV block (paged). Must divide max_seq_len +
    # max_new_tokens. Smaller blocks waste less tail (internal
    # fragmentation ~ block_size/2 tokens per request) but lengthen the
    # block table; 16 suits the default shapes.
    kv_block_size: int = 16
    # Physical blocks in the paged pool. 0 = dense-parity sizing
    # (batch_size * total_len / kv_block_size): same worst case as
    # dense. Set explicitly to cap KV HBM — admission then defers
    # instead of overcommitting.
    kv_pool_blocks: int = 0
    # KV residency precision (paged layout). "fp" keeps the model dtype
    # — greedy outputs bitwise-identical to dense, the pinned-accuracy
    # default. "int8" quantizes blocks (per-position per-head abs-max
    # scales, dequantized at read): ~2x blocks per HBM byte at a pinned
    # greedy-token tolerance; size kv_pool_blocks up accordingly.
    kv_dtype: str = "fp"
    # Fused block-table attention for the paged decode step: walk the
    # table inside the attention kernel (int8 dequantized in-register)
    # instead of gathering the dense [slots, total_len] KV view every
    # step. Off by default — the gather path is the bitwise-parity
    # reference; fused numerics are f32-equivalent, not bitwise.
    kv_fused: bool = False
    # Default wait (seconds) for StreamHandle.tokens()/result() when the
    # caller passes none — raise it when memory-deferred admissions
    # under load would spuriously time callers out.
    stream_timeout_s: float = 60.0
    # Disaggregated-fleet role: "" (colocated), "prefill" (prompt
    # admission only — decode peers pull finished prompt KV via the
    # :prefill/:import handoff endpoints) or "decode" (resumes imported
    # prompts). Requires kv_layout="paged"; surfaces as the
    # `serving_role` exposition label so per-pool dashboards and the
    # operator scrape can tell the pools apart.
    serving_role: str = ""
    # Tensor-parallel shards per replica (continuous mode): >1 runs the
    # decoder over a tp-wide tensor mesh — weights Megatron-split by the
    # model's partition rules, the KV pool sharded over the KV-head
    # axis (block ids stay host-global, so the prefix trie, allocator
    # refcount/CoW, and the prefill→decode handoff are unchanged). Must
    # divide the model's n_kv_heads / n_heads / d_ff; the serving pod
    # needs tp chips. The `serving_kv_bytes_*` gauges then price the
    # pool PER CHIP.
    tp_shards: int = 1
    # Long-context serving (continuous mode, paged layout). Chunked
    # prefill: >0 admits any prompt whose (post-prefix-hit) suffix
    # exceeds this as a CHAIN of bounded chunk dispatches interleaved
    # with decode rounds — the chunk width bounds the worst-case gap a
    # long admission inserts into live streams' inter-token cadence.
    # Token streams stay byte-identical to monolithic prefill. Must be
    # <= max_seq_len; 0 disables (monolithic admission, pre-chunking
    # behavior).
    prefill_chunk_tokens: int = 0
    # Prompt-length ceiling. 0 = max_seq_len (the compiled prefill
    # width). Raising it past max_seq_len requires
    # prefill_chunk_tokens > 0: chunks ride the paged block scatter, so
    # only the virtual KV row — not any compiled shape — bounds the
    # prompt. Prompts beyond the ceiling are rejected with HTTP 413
    # (never silently truncated). Sizes the KV row: total = this +
    # max_new_tokens; kv_block_size must divide it.
    max_prompt_len: int = 0
    # Context-parallel shards (continuous mode): >1 adds a `sequence`
    # mesh axis and runs each prefill chunk's attention ring-style
    # across it (parallel/ring_attention.py collective-permute core
    # over the gathered paged span) — prefill FLOPs/bandwidth for long
    # prompts scale with cp while decode stays tp-only. Requires
    # prefill_chunk_tokens > 0 and the paged gather path (not
    # kv_fused); pow2; the pod needs tp*cp*pp chips.
    cp_shards: int = 1
    # Pipeline-parallel decoder stages: >1 shards the stacked layer
    # weights AND the KV pool's leading layer dim over the outermost
    # `pipeline` mesh axis — per-chip weight and KV bytes divide by pp
    # (long contexts fit where a tp-only replica OOMs) while block ids
    # stay host-global (allocator/trie/handoff unchanged). Must divide
    # the model's n_layers; the pod needs tp*cp*pp chips.
    pp_stages: int = 1
    # Host-RAM KV tier budget in bytes (paged layout; 0 disables).
    # Prefix-trie evictions DEMOTE their blocks here instead of freeing
    # outright, trie misses probe it before cold prefill (second-chance
    # cache — effective pool size rises past HBM at equal device
    # bytes), and QoS suspensions park live streams' KV here until
    # resume.
    host_kv_bytes: int = 0
    # Fleet KV economy: distinct affinity keys the prefix→holder
    # directory tracks (paged layout; 0 disables the economy — the
    # tiers above stay replica-private). With a directory, the miss
    # path runs trie → host → peer → cold → prefill: local misses
    # probe directory hints, pull the deepest advertised prefix from
    # the holding peer over the PR-9 handoff envelope (:kv endpoint),
    # and prefill only the tail.
    kv_directory_size: int = 0
    # Shared cold content-addressed store ref ("mem://<name>[?bytes=n]";
    # empty disables). Host-tier evictions demote their payload here
    # before dropping bytes; the weights epoch rides the content key,
    # so a live weight push invalidates every pre-swap blob by
    # construction.
    cold_store_ref: str = ""
    # Recompute-vs-import crossover: minimum prefill tokens a remote
    # (peer/cold) import must save over the best LOCAL tier before the
    # pull is worth its fixed cost (RTT + envelope codec + scatter).
    # 0 = import any strictly deeper match.
    kv_import_crossover_tokens: int = 0
    # Multi-tenant QoS tenants: "name=weight[:rate[:burst[:priority]]]"
    # comma-separated (serving/qos.py:parse_tenants). Empty disables
    # QoS entirely — FIFO admission, one implicit tenant, exactly the
    # pre-QoS decoder. With tenants set, submits carry
    # tenant/priority/deadline (gateway X-Tenant/X-Priority/
    # X-Deadline-Ms headers), token buckets 429 over-rate tenants, and
    # the pop loop orders by weighted fair share + aged priority.
    qos_tenants: str = ""
    # Seconds of queue wait worth one priority point (starvation
    # aging); <= 0 disables aging.
    qos_aging_s: float = 30.0
    # Flash-crowd elasticity: peer weight birth. Comma-separated donor
    # addresses ("host:port,host:port" — serving peers of the same
    # model). When set, boot pulls the param pytree from the first
    # answering donor over the chunked :pull envelope instead of
    # touching the checkpoint store — the weights arrive already at the
    # fleet's live epoch, so a newborn joining mid-rollout is
    # version-consistent by construction. Donors are tried in order; a
    # donor dying mid-stream falls through to the next, and an empty
    # chain falls back to checkpoint_dir (a newborn always comes up).
    weight_peers: str = ""
    # Per-donor transport timeout for the birth pull.
    weight_pull_timeout_s: float = 30.0
    # Persistent compile cache directory (shared volume across a pool's
    # replicas; empty disables). The server pre-warms the decode
    # dispatch set at start, pointed at this directory — see
    # serving/compile_cache.py for the fingerprint/invalidation scheme.
    compile_cache_dir: str = ""
    # Compute dtype override ("bfloat16"/"float32"); empty keeps the
    # model preset's dtype. The tpu-serving manifest's --dtype arg.
    dtype: str = ""


def _predict_impl(model: ModelSpec, params, inputs):
    cfg = model.config
    if model.family == "transformer":
        logits = model.apply(params, inputs["tokens"], cfg)
        # Causality makes position len-1 exact regardless of padding
        # after it — gather each request's last real position.
        last = jnp.take_along_axis(
            logits, inputs["last_index"][:, None, None], axis=1
        )[:, 0]
        return {
            "logits": last.astype(jnp.float32),
            "next_token": jnp.argmax(last, axis=-1),
        }
    if model.family == "bert":
        seq, pooled = model.apply(
            params, inputs["tokens"], cfg,
            pad_mask=inputs.get("pad_mask"),
        )
        return {"pooled": pooled.astype(jnp.float32)}
    if model.family == "resnet":
        logits = model.apply(params, inputs["images"], cfg)
        return {
            "probabilities": jax.nn.softmax(logits, axis=-1),
            "classes": jnp.argmax(logits, axis=-1),
        }
    raise ValueError(model.family)


# One jitted predict wrapper per (model, dtype): jax.jit over a bound
# method is a fresh wrapper — and a fresh executable — per engine
# instance, so a flash-crowd newborn in the same process would re-pay
# the lockstep predict compile its donor already paid. Sharing the
# wrapper makes the whole dispatch surface executable-cached the way
# the decoder's module-level jits already are; across processes the
# persistent XLA cache (utils/jaxenv.place_compile_cache) covers it.
_PREDICT_JIT: dict[tuple[str, str], object] = {}


class InferenceEngine:
    """Thread-safe predict over a fixed-shape compiled function."""

    def __init__(self, cfg: EngineConfig):
        self.cfg = cfg
        overrides = {"dtype": jnp.dtype(cfg.dtype)} if cfg.dtype else {}
        self.model: ModelSpec = get_model(cfg.model, **overrides)
        self._lock = threading.Lock()
        # Replica-birth accounting: where the boot weights came from
        # ("peer" / "checkpoint" / "init"), the donor's weights epoch
        # (0 = boot weights, checkpoint semantics), and the per-phase
        # cold-start seconds the server publishes as
        # serving_cold_start_seconds{phase}.
        self.weight_pull_source = "init"
        self.boot_weights_version = 0
        self.cold_start: dict[str, float] = {}
        import time as _time

        t0 = _time.perf_counter()
        self.params = self._load_params()
        self.cold_start["weights"] = _time.perf_counter() - t0
        jit_key = (cfg.model, cfg.dtype or "")
        if jit_key not in _PREDICT_JIT:
            import functools

            _PREDICT_JIT[jit_key] = jax.jit(
                functools.partial(_predict_impl, self.model))
        self._predict = _PREDICT_JIT[jit_key]
        self._seed = 0
        self._warm = False

    def _pull_params_from_peers(self):
        """Peer weight birth: try each configured donor in order over
        the chunked ``:pull`` envelope. Returns the assembled params
        (stamping source/epoch) or None when every donor is dead — the
        caller then falls back to the checkpoint path, so a newborn
        always comes up."""
        from kubeflow_tpu.serving import weights as weights_mod

        # Only the tree's structure is needed: no second set of weights.
        reference = jax.eval_shape(lambda: self.model.init(
            jax.random.PRNGKey(0), self.model.config))
        for donor in [p.strip() for p in self.cfg.weight_peers.split(",")
                      if p.strip()]:
            try:
                leaves, version, _has_draft = weights_mod.pull_weights(
                    donor, self.cfg.model,
                    timeout=self.cfg.weight_pull_timeout_s)
                model_leaves, _ = weights_mod.split_namespaces(leaves)
                params = weights_mod.unflatten_params(model_leaves,
                                                      reference)
            except (OSError, ValueError) as e:
                # Dead / mid-stream-dying / misbehaving donor: the
                # assembler guarantees nothing partial survived; move
                # to the next donor.
                import logging

                logging.getLogger(__name__).warning(
                    "weight pull from donor %s failed: %s", donor, e)
                continue
            self.weight_pull_source = "peer"
            self.boot_weights_version = int(version)
            return params
        return None

    @staticmethod
    def _normalize_placement(params):
        """Land the boot weights as uncommitted default-device arrays —
        the same flavor ``update_weights`` installs — regardless of
        birth path. The jit executable cache keys on array sharding as
        well as avals: a checkpoint restore hands back COMMITTED
        arrays while a peer pull hands back host numpy, and without
        this normalization a newborn recompiles executables its donor
        (or the persistent compile cache) already holds."""
        return jax.device_put(jax.tree.map(np.asarray, params))

    def _load_params(self):
        """The tree this replica holds for the life of the process. The
        transformer family holds it at the compute dtype
        (``transformer.serving_params``): a float32 init or checkpoint
        is cast here, once, before the tree moves through the host (half
        the bytes), and no float32 copy is kept; a donor's ``:pull``
        already carries the held dtype. It keeps the checkpoint's names
        and layout until a decoder takes it (``ModelServer.decoder``
        transposes the stacked q/k/v then): a caller that swaps the
        engine's tree before that reads it by them. Other families'
        trees pass as they are."""
        cfg = self.model.config
        if self.model.family == "transformer":
            from kubeflow_tpu.models.transformer import (
                serving_params as held,
            )
        else:
            def held(params, cfg, **_):
                return params
        if self.cfg.weight_peers:
            params = self._pull_params_from_peers()
            if params is not None:
                return self._normalize_placement(
                    held(params, cfg, transpose_qkv=False))
        if not self.cfg.checkpoint_dir:
            # Built in the call: the helper is the float32 tree's only
            # holder and lets each leaf go as its copy exists.
            return self._normalize_placement(
                held(self.model.init(jax.random.PRNGKey(0), cfg), cfg,
                     transpose_qkv=False))
        from kubeflow_tpu.train import checkpoint as ckpt_lib
        from kubeflow_tpu.train.optimizers import OptimizerConfig
        from kubeflow_tpu.train.trainer import init_state

        state = init_state(
            jax.random.PRNGKey(0), self.model, OptimizerConfig()
        )
        abstract = jax.eval_shape(lambda: state)
        restored = ckpt_lib.restore_latest(self.cfg.checkpoint_dir,
                                           abstract)
        if restored is None:
            raise FileNotFoundError(
                f"no checkpoint under {self.cfg.checkpoint_dir}"
            )
        self.weight_pull_source = "checkpoint"
        return self._normalize_placement(
            held(restored[0].params, cfg, transpose_qkv=False))

    def relay_for_decoder(self):
        """The engine's tree as a decoder holds it, kept as the engine's
        own so that the two hold one tree: the stacked q/k/v transposed
        (``transformer.serving_params``), made before the decoder
        allocates its K/V. The helper is handed the only reference, so
        each checkpoint-layout leaf goes as its transpose exists: one
        leaf of transient, not a second stack. Under the predict lock,
        which no predict reads the tree outside of."""
        from kubeflow_tpu.models.transformer import serving_params

        with self._lock:
            self.params = serving_params(self._hand_over_params(),
                                         self.model.config)
            return self.params

    def _hand_over_params(self):
        """``self.params`` with the attribute cleared, so that the callee
        this is passed to holds the tree's only reference."""
        params, self.params = self.params, None
        return params

    # ------------------------------------------------------------------

    def _predict_fn(self, params, inputs):
        return _predict_impl(self.model, params, inputs)

    def warmup(self) -> None:
        self.predict_batch(self._example_instances(1))
        self._warm = True

    @property
    def ready(self) -> bool:
        return self._warm

    def validate_instance(self, inst: dict) -> None:
        """Reject malformed instances before they reach a batch (an empty
        'tokens' list would wrap last_index to -1 and return garbage logits
        with 200 OK)."""
        if not isinstance(inst, dict):
            raise ValueError("each instance must be an object")
        if self.model.family in ("transformer", "bert"):
            toks = inst.get("tokens")
            if not isinstance(toks, list) or not toks:
                raise ValueError(
                    "each instance needs a non-empty 'tokens' list"
                )
            if not all(isinstance(t, int) and not isinstance(t, bool)
                       for t in toks):
                raise ValueError("'tokens' must be a flat list of ints")
            want = inst.get("max_new_tokens", 0)
            if not isinstance(want, int) or want < 0:
                raise ValueError("'max_new_tokens' must be a non-negative int")
            if want > self.cfg.max_new_tokens:
                raise ValueError(
                    f"'max_new_tokens' {want} exceeds server limit "
                    f"{self.cfg.max_new_tokens}"
                )
            temp = inst.get("temperature", 0.0)
            if not isinstance(temp, (int, float)) or temp < 0:
                raise ValueError("'temperature' must be a non-negative number")
        elif self.model.family == "resnet":
            if "images" not in inst:
                raise ValueError("each instance needs 'images'")
            cfg = self.model.config
            try:
                arr = np.asarray(inst["images"], np.float32)
            except (TypeError, ValueError) as e:
                raise ValueError(f"'images' not numeric: {e}") from None
            want = (cfg.image_size, cfg.image_size, 3)
            if arr.shape != want:
                raise ValueError(
                    f"'images' shape {arr.shape} != expected {want}"
                )

    def _example_instances(self, n: int) -> list[dict]:
        cfg = self.model.config
        if self.model.family in ("transformer", "bert"):
            return [{"tokens": [0] * 8}] * n
        return [{"images": np.zeros(
            (cfg.image_size, cfg.image_size, 3)).tolist()}] * n

    # ------------------------------------------------------------------

    def _pad_tokens(self, instances: list[dict]) -> dict:
        b = self.cfg.batch_size
        t = self.cfg.max_seq_len
        tokens = np.zeros((b, t), np.int32)
        mask = np.zeros((b, t), np.float32)
        for i, inst in enumerate(instances):
            seq = np.asarray(inst["tokens"], np.int32)[:t]
            tokens[i, : len(seq)] = seq
            mask[i, : len(seq)] = 1.0
        return {"tokens": tokens, "pad_mask": mask}

    def _generate_batch(self, instances: list[dict]) -> list[dict]:
        """Autoregressive path: prefill + KV-cache decode in one compiled
        call; per-row temperature, per-row requested length sliced out."""
        from kubeflow_tpu.models.decode import generate

        n = len(instances)
        b, t = self.cfg.batch_size, self.cfg.max_seq_len
        tokens = np.zeros((b, t), np.int32)
        lengths = np.ones((b,), np.int32)
        temperature = np.zeros((b,), np.float32)
        for i, inst in enumerate(instances):
            seq = np.asarray(inst["tokens"], np.int32)[:t]
            tokens[i, : len(seq)] = seq
            lengths[i] = len(seq)
            temperature[i] = float(inst.get("temperature", 0.0))
        row_valid = np.zeros((b,), bool)
        row_valid[:n] = True
        with self._lock:
            self._seed += 1
            toks, last = generate(
                self.params, jnp.asarray(tokens), jnp.asarray(lengths),
                self.model.config,
                max_new_tokens=self.cfg.max_new_tokens,
                key=jax.random.PRNGKey(self._seed),
                temperature=jnp.asarray(temperature),
                top_k=self.cfg.top_k,
                row_valid=jnp.asarray(row_valid),
            )
        toks = np.asarray(toks)[:n]
        last = np.asarray(last)[:n]
        out = []
        for i, inst in enumerate(instances):
            want = min(int(inst.get("max_new_tokens", 0)),
                       self.cfg.max_new_tokens)
            pred = {
                "next_token": int(toks[i, 0]) if want else
                int(np.argmax(last[i])),
                "tokens": toks[i, :want].tolist(),
            }
            # Full-vocab logits are huge as JSON (32k floats/row); include
            # them only for plain predicts or on explicit request.
            if not want or inst.get("return_logits"):
                pred["logits"] = last[i].tolist()
            out.append(pred)
        return out

    def predict_batch(self, instances: list[dict]) -> list[dict]:
        """Pad instances to the server batch, run, slice real results."""
        if len(instances) > self.cfg.batch_size:
            raise ValueError(
                f"batch {len(instances)} exceeds limit {self.cfg.batch_size}"
            )
        n = len(instances)
        if (self.model.family == "transformer"
                and any(inst.get("max_new_tokens") for inst in instances)):
            return self._generate_batch(instances)
        if self.model.family in ("transformer", "bert"):
            batch = self._pad_tokens(instances)
            if self.model.family == "transformer":
                batch.pop("pad_mask")
                lengths = [
                    min(len(inst["tokens"]), self.cfg.max_seq_len)
                    for inst in instances
                ] + [1] * (self.cfg.batch_size - n)
                batch["last_index"] = np.asarray(lengths, np.int32) - 1
        else:
            cfg = self.model.config
            images = np.zeros(
                (self.cfg.batch_size, cfg.image_size, cfg.image_size, 3),
                np.float32,
            )
            for i, inst in enumerate(instances):
                images[i] = np.asarray(inst["images"], np.float32)
            batch = {"images": images}

        with self._lock:
            out = self._predict(self.params, batch)
        out = jax.tree.map(lambda x: np.asarray(x)[:n], out)
        return [
            {k: v[i].tolist() for k, v in out.items()} for i in range(n)
        ]

    def metadata(self) -> dict:
        cfg = self.model.config
        return {
            "name": self.cfg.model,
            "family": self.model.family,
            "batch_size": self.cfg.batch_size,
            "config": {
                k: str(v) for k, v in vars(cfg).items()
            },
        }
