"""REST model server.

The http-proxy surface (components/k8s-model-server/http-proxy/server.py:
PredictHandler :251, metadata :154) served directly from the TPU process:

- ``POST /v1/models/<name>:predict``  {"instances": [...]} → {"predictions": [...]}
- ``GET  /v1/models/<name>``          model metadata + availability
- ``GET  /healthz`` ``GET /readyz``   liveness/readiness (probe target,
  tf-serving-template.libsonnet:70-75)
- ``GET  /monitoring/prometheus/metrics`` request counters/latency
  (tf-serving-template.libsonnet:127-130)
"""

from __future__ import annotations

import json
import threading
import time
from http.client import HTTPConnection
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from kubeflow_tpu.observability.metrics import (
    MetricRegistry,
    render_prometheus,
)
from kubeflow_tpu.observability.tracing import (
    REQUEST_ID_HEADER,
    gen_request_id,
    render_debug,
    render_rounds,
)
from kubeflow_tpu.serving.batcher import DynamicBatcher
from kubeflow_tpu.serving.continuous import PromptTooLong
from kubeflow_tpu.serving.engine import EngineConfig, InferenceEngine
from kubeflow_tpu.serving.qos import QosRejected


class _Metrics:
    """Server-level request metrics on the shared registry: request and
    error counters plus a latency *histogram* (the old renderer exposed a
    sum/count summary — no percentiles, and its own copy of the text
    format)."""

    def __init__(self) -> None:
        self.registry = MetricRegistry()
        self._requests = self.registry.counter(
            "serving_requests_total", "HTTP requests handled")
        self._errors = self.registry.counter(
            "serving_errors_total", "HTTP requests that failed")
        self._latency = self.registry.histogram(
            "serving_latency_seconds", "End-to-end request latency")
        # Cold-start surface (flash-crowd elasticity): where this
        # replica's boot weights came from, and the per-phase birth
        # timing the ≥5x cold-to-first-token gate reads.
        self._weight_pulls = self.registry.counter(
            "serving_weight_pulls_total",
            "Boot weight installs by source (peer = pulled from a "
            "serving donor over :pull; checkpoint = restored from the "
            "store; init = fresh random init)", labels=("source",))
        self._cold_start = self.registry.gauge(
            "serving_cold_start_seconds",
            "Birth phase durations: weights (install), compile "
            "(dispatch-set warm), first_token (boot to serving-ready)",
            labels=("phase",))

    def observe(self, seconds: float, error: bool) -> None:
        self._requests.inc()
        if error:
            self._errors.inc()
        self._latency.observe(seconds)

    def record_weight_pull(self, source: str) -> None:
        self._weight_pulls.labels(source or "init").inc()

    def record_cold_start(self, phases: dict) -> None:
        for phase, seconds in phases.items():
            self._cold_start.labels(phase).set(float(seconds))

    def render(self) -> str:
        return self.registry.render()


class ModelServer:
    """Dual-port model server: REST on ``port`` (:8500 by convention), gRPC
    on ``grpc_port`` (:9000; None disables, 0 binds an ephemeral port for
    tests) — the tf-serving port contract
    (tf-serving-template.libsonnet:43-49). Both ports share one engine and
    one dynamic batcher, so mixed-protocol traffic coalesces into the same
    TPU batches."""

    def __init__(self, engine_cfg: EngineConfig, *, port: int = 8500,
                 grpc_port: int | None = None,
                 batch_timeout_ms: float = 5.0):
        self._t_boot = time.perf_counter()
        self.engine = InferenceEngine(engine_cfg)
        self.batcher = DynamicBatcher(
            self.engine.predict_batch, engine_cfg.batch_size, batch_timeout_ms
        )
        self.metrics = _Metrics()
        self.port = port
        self.grpc_port = grpc_port
        self._httpd: ThreadingHTTPServer | None = None
        self._grpc = None
        # Generation rides the continuous-batching decoder (per-request
        # lengths decoupled, tokens streamable); plain predicts keep the
        # dynamic batcher. Lazily built: non-LM servers never pay for it.
        self._decoder = None
        self._decoder_lock = threading.Lock()
        # Live weight pushes (:weights endpoint): chunk assembly state,
        # serialized so concurrent learner chunks interleave safely.
        self._weights_assembler = None
        self._weights_lock = threading.Lock()
        # Donor-side pull export (:pull endpoint): the flattened host
        # copy of the current epoch's tree, chunk-planned once and
        # re-served to every concurrent newborn; invalidated by version
        # compare when a live push swaps epochs. Leaf lock guarding only
        # the cached tuple (the flatten/pack work runs outside it).
        self._export_cache = None
        self._export_lock = threading.Lock()
        # Readiness ramp: True from construction until warm() covers
        # the boot path — /healthz answers {"status": "warming"} so the
        # gateway route-excludes this replica without failure-counter
        # penalty while it compiles. A warm that fails leaves its error
        # text here: /healthz then answers 500 {"status": "failed"} and
        # serve_forever ends the process.
        self.warming = True
        self.warm_error: str | None = None

    @property
    def _generates(self) -> bool:
        """Whether generation goes to a ContinuousDecoder here."""
        return (self.engine.model.family == "transformer"
                and self.engine.cfg.max_new_tokens > 0
                and self.engine.cfg.decode_mode == "continuous")

    @property
    def decoder(self):
        if not self._generates:
            return None
        with self._decoder_lock:
            if self._decoder is None:
                from kubeflow_tpu.serving.cold_store import (
                    cold_store_from_ref,
                )
                from kubeflow_tpu.serving.continuous import ContinuousDecoder
                from kubeflow_tpu.serving.kv_directory import KvDirectory
                from kubeflow_tpu.serving.qos import QosPolicy

                qos = (QosPolicy(self.engine.cfg.qos_tenants,
                                 aging_seconds=self.engine.cfg.qos_aging_s)
                       if self.engine.cfg.qos_tenants else None)
                # Fleet KV economy: a sized directory turns the local
                # tiers into fleet-visible ones; the cold ref names the
                # shared content-addressed store (colocated replicas
                # resolving the same mem:// name share one instance).
                # The peer-fetch transport is installed by whichever
                # fleet wraps this server (in-process: DecoderFleet;
                # cross-pod: RemoteActorFleet.fetch_kv against :kv).
                kv_dir = (KvDirectory(self.engine.cfg.kv_directory_size)
                          if self.engine.cfg.kv_directory_size > 0
                          else None)
                self._decoder = ContinuousDecoder(
                    self.engine.relay_for_decoder(),
                    self.engine.model.config,
                    slots=self.engine.cfg.batch_size,
                    prefill_len=self.engine.cfg.max_seq_len,
                    max_new_tokens=self.engine.cfg.max_new_tokens,
                    top_k=self.engine.cfg.top_k,
                    eos_id=self.engine.cfg.eos_id,
                    chunk_size=self.engine.cfg.decode_chunk,
                    prefix_cache_slots=self.engine.cfg.prefix_cache_slots,
                    prefix_cache_min_len=(
                        self.engine.cfg.prefix_cache_min_len),
                    prefill_len_buckets=self.engine.cfg.prefill_len_buckets,
                    speculative_k=self.engine.cfg.speculative_k,
                    draft_mode=self.engine.cfg.draft_mode,
                    kv_layout=self.engine.cfg.kv_layout,
                    kv_block_size=self.engine.cfg.kv_block_size,
                    kv_pool_blocks=self.engine.cfg.kv_pool_blocks,
                    kv_dtype=self.engine.cfg.kv_dtype,
                    kv_fused=self.engine.cfg.kv_fused,
                    stream_timeout_s=self.engine.cfg.stream_timeout_s,
                    role=self.engine.cfg.serving_role,
                    tp_shards=self.engine.cfg.tp_shards,
                    qos=qos,
                    host_kv_bytes=self.engine.cfg.host_kv_bytes,
                    prefill_chunk_tokens=(
                        self.engine.cfg.prefill_chunk_tokens),
                    max_prompt_len=self.engine.cfg.max_prompt_len,
                    cp_shards=self.engine.cfg.cp_shards,
                    pp_stages=self.engine.cfg.pp_stages,
                    kv_directory=kv_dir,
                    cold_store=cold_store_from_ref(
                        self.engine.cfg.cold_store_ref),
                    kv_import_crossover_tokens=(
                        self.engine.cfg.kv_import_crossover_tokens),
                    replica_name=(
                        f"{self.engine.cfg.model}:{self.port}"),
                    boot_weights_version=self.engine.boot_weights_version,
                    compile_cache_dir=self.engine.cfg.compile_cache_dir,
                )
            return self._decoder

    # ------------------------------------------------------------------

    def handle_predict(self, name: str, body: dict,
                       request_id: str | None = None,
                       qos: dict | None = None) -> dict:
        if name != self.engine.cfg.model:
            raise KeyError(f"model {name!r} not served")
        instances = body.get("instances")
        if not isinstance(instances, list) or not instances:
            raise ValueError("body must contain non-empty 'instances'")
        for inst in instances:
            self.engine.validate_instance(inst)
        qos = qos or {}
        # Generation requests go to the continuous decoder (per-request
        # lengths are decoupled — a short request returns as soon as ITS
        # tokens are done); plain predicts coalesce in the dynamic batcher.
        handles = []
        for i, inst in enumerate(instances):
            if inst.get("max_new_tokens") and self.decoder is not None:
                # One HTTP request id; multi-instance bodies suffix the
                # instance index so each stream's timeline stays unique.
                rid = (request_id if request_id and i == 0
                       else f"{request_id}-{i}" if request_id else None)
                handles.append(("gen", inst, self.decoder.submit(
                    inst["tokens"], inst["max_new_tokens"],
                    float(inst.get("temperature", 0.0)),
                    request_id=rid, **qos,
                )))
            else:
                handles.append(("batch", inst,
                                self.batcher.submit_async(inst)))
        preds = []
        for kind, inst, h in handles:
            if kind == "gen":
                preds.append(self._gen_prediction(inst, h.result(
                    with_logits=bool(inst.get("return_logits")) or None,
                )))
            else:
                preds.append(self.batcher.collect(h))
        return {"predictions": preds}

    @staticmethod
    def _gen_prediction(inst: dict, res: dict) -> dict:
        """Shape a decoder result like the lockstep generate path did
        (engine._generate_batch), so clients see one schema either way."""
        import numpy as np

        toks = res["tokens"]
        pred = {
            "next_token": int(toks[0]) if toks
            else int(np.argmax(res["prefill_logits"])),
            "tokens": toks,
            "finish_reason": res["finish_reason"],
        }
        if not toks or inst.get("return_logits"):
            pred["logits"] = res["prefill_logits"].tolist()
        return pred

    def handle_predict_stream(self, name: str, body: dict,
                              request_id: str | None = None,
                              qos: dict | None = None):
        """Streaming generation: yields JSON-line dicts, one per token, then
        a terminal ``{"done": true, ...}`` record. Exactly one instance per
        stream (the chunked-HTTP / gRPC-stream unit is a single sequence)."""
        if name != self.engine.cfg.model:
            raise KeyError(f"model {name!r} not served")
        instances = body.get("instances")
        if not isinstance(instances, list) or len(instances) != 1:
            raise ValueError("streaming needs exactly one instance")
        inst = instances[0]
        self.engine.validate_instance(inst)
        if not inst.get("max_new_tokens"):
            raise ValueError("streaming needs 'max_new_tokens' > 0")
        if self.decoder is None:
            raise ValueError("model does not support generation")
        handle = self.decoder.submit(
            inst["tokens"], inst["max_new_tokens"],
            float(inst.get("temperature", 0.0)),
            request_id=request_id, **(qos or {}),
        )

        # Validation above runs eagerly (before the HTTP 200 goes out); only
        # the token iteration is deferred.
        def _records():
            index = 0
            for tok in handle.tokens():
                yield {"token": tok, "index": index}
                index += 1
            res = handle.result()
            yield {
                "done": True,
                "tokens": res["tokens"],
                "finish_reason": res["finish_reason"],
                "ttft_ms": round(1000 * (res["ttft_s"] or 0.0), 3),
            }

        return _records()

    # -- disaggregated prefill/decode handoff --------------------------
    #
    # The HTTP face of ContinuousDecoder.export_prompt/import_prompt:
    # a PREFILL-pool server answers ``:prefill`` by computing the
    # prompt's KV and (when ``handoff_to`` names a decode server)
    # pushing the packed block payload server-to-server at that peer's
    # ``:import`` — the KV bytes never transit the gateway, which only
    # orchestrates the two hops and then relays the ordinary
    # ``:predict`` to the decode server, where it prefix-hits the
    # imported blocks.

    def handle_prefill(self, name: str, body: dict,
                       request_id: str | None = None) -> dict:
        from kubeflow_tpu.serving import handoff as handoff_mod

        if name != self.engine.cfg.model:
            raise KeyError(f"model {name!r} not served")
        instances = body.get("instances")
        if not isinstance(instances, list) or len(instances) != 1:
            raise ValueError("prefill handoff needs exactly one instance")
        inst = instances[0]
        self.engine.validate_instance(inst)
        if self.decoder is None:
            raise ValueError("model does not support generation")
        h = self.decoder.export_prompt(inst["tokens"])
        env = handoff_mod.pack(h)
        target = str(body.get("handoff_to", "") or "")
        if target:
            pushed = self._push_handoff(name, target, env, request_id)
            return {"handoff": pushed, "prefix_len": h["prefix_len"]}
        # No destination: hand the envelope back to the caller (tests /
        # out-of-band relays).
        return {"handoff": False, "prefix_len": h["prefix_len"],
                "envelope": env}

    def _push_handoff(self, name: str, target: str, env: dict,
                      request_id: str | None = None) -> bool:
        """POST the packed payload at the decode server's ``:import``.
        Best-effort: any failure returns False — the decode server will
        simply prefill the prompt itself (degraded, never wrong)."""
        host, _, port_s = target.partition(":")
        data = json.dumps(env).encode()
        headers = {"Content-Type": "application/json"}
        if request_id:
            headers[REQUEST_ID_HEADER] = request_id
        try:
            conn = HTTPConnection(host, int(port_s or 80), timeout=30.0)
            try:
                conn.request("POST", f"/v1/models/{name}:import",
                             body=data, headers=headers)
                resp = conn.getresponse()
                out = json.loads(resp.read() or b"{}")
                return resp.status == 200 and bool(out.get("imported"))
            finally:
                conn.close()
        except (OSError, ValueError):
            return False

    def handle_import(self, name: str, body: dict) -> dict:
        from kubeflow_tpu.serving import handoff as handoff_mod

        if name != self.engine.cfg.model:
            raise KeyError(f"model {name!r} not served")
        if self.decoder is None:
            raise ValueError("model does not support generation")
        h = handoff_mod.unpack(body)  # ValueError on garbage -> 400
        return {"imported": bool(self.decoder.import_prompt(h))}

    def handle_kv(self, name: str, body: dict) -> dict:
        """The fleet KV economy's pull endpoint (``:kv``): a peer
        replica that saw this server advertised in the prefix directory
        POSTs its prompt here and gets back the deepest cached prefix
        as a packed handoff envelope plus the weights epoch that
        computed it — the requester validates both and refuses stale or
        mismatched envelopes. A prefix this server no longer caches is
        a KeyError (HTTP 404): the hint was stale, the requester
        withdraws it and falls through to the cold tier or a plain
        prefill."""
        from kubeflow_tpu.serving import handoff as handoff_mod

        if name != self.engine.cfg.model:
            raise KeyError(f"model {name!r} not served")
        if self.decoder is None:
            raise ValueError("model does not support generation")
        toks = body.get("tokens")
        if not isinstance(toks, list) or not toks:
            raise ValueError("kv pull needs non-empty 'tokens'")
        h = self.decoder.export_prefix(toks)  # KeyError -> 404 on miss
        ver = h.pop("weights_version", 0)
        return {"envelope": handoff_mod.pack(h),
                "weights_version": ver,
                "prefix_len": h["prefix_len"]}

    # -- live weight streaming -----------------------------------------
    #
    # The HTTP face of ContinuousDecoder.update_weights: a learner
    # POSTs chunked weight envelopes (serving/weights.py) directly at
    # each replica's ``:weights`` — server-to-server, the gateway never
    # relays weight bytes. Chunks assemble per weights epoch; the swap
    # installs atomically only when the last chunk lands, so a torn or
    # abandoned push can never reach the decoder.

    def handle_weights(self, name: str, body: dict) -> dict:
        from kubeflow_tpu.serving import weights as weights_mod

        if name != self.engine.cfg.model:
            raise KeyError(f"model {name!r} not served")
        decoder = self.decoder
        if decoder is None:
            raise ValueError("model does not support generation")
        chunk = weights_mod.unpack_chunk(body)  # ValueError -> 400
        with self._weights_lock:
            if self._weights_assembler is None:
                self._weights_assembler = weights_mod.WeightChunkAssembler()
            done = self._weights_assembler.add(chunk)
            pending = self._weights_assembler.pending
        if done is None:
            return {"installed": False, "pending": pending,
                    "weights_version": chunk["weights_version"]}
        leaves, has_draft = done
        model_leaves, draft_leaves = weights_mod.split_namespaces(leaves)
        params = weights_mod.unflatten_params(model_leaves,
                                              decoder.params)
        draft = None
        if has_draft:
            spec = getattr(decoder, "_spec", None)
            if spec is None or not hasattr(spec, "params"):
                raise ValueError(
                    "push carries draft weights but no draft-model "
                    "proposer is configured")
            draft = weights_mod.unflatten_params(draft_leaves,
                                                 spec.params)
        installed = decoder.update_weights(
            params, version=chunk["weights_version"], draft_params=draft)
        return {"installed": True, "weights_version": installed}

    def handle_weights_pull(self, name: str, body: dict) -> dict:
        """Donor side of peer weight birth (``:pull``): a NEWBORN
        replica POSTs ``{"seq": k}`` and gets back chunk ``k`` of this
        server's CURRENT weights epoch as a standard push envelope —
        the PR-15 transport's reverse direction, so the newborn's
        weights arrive already at the fleet's version and no checkpoint
        store sits on the scale-up hot path.

        The flattened host tree is chunk-planned once per epoch and
        cached (``_export_cache``); a live push swapping epochs
        mid-pull changes the version the next chunk carries, which the
        puller's assembler treats exactly like a superseded push —
        restart, never a mixed-epoch install. A ``seq`` beyond the
        chunk count is a KeyError (404): the puller overshot a
        shrinking plan after an epoch swap and will restart."""
        from kubeflow_tpu.serving import weights as weights_mod

        if name != self.engine.cfg.model:
            raise KeyError(f"model {name!r} not served")
        seq = int(body.get("seq", 0))
        # A decoder (live-pushable) serves its epoch-consistent
        # snapshot; a plain predict server donates the engine's boot
        # tree at the epoch it booted with.
        with self._decoder_lock:
            decoder = self._decoder
        if decoder is not None:
            params, version = decoder.weights_snapshot()
        else:
            params = self.engine.params
            version = self.engine.boot_weights_version
        with self._export_lock:
            cache = self._export_cache
        if cache is None or cache[0] != version:
            # Flatten + plan OUTSIDE the lock (device fetches and a
            # full host copy must not serialize concurrent pulls; a
            # losing racer just rebuilds the same plan).
            items = weights_mod.flatten_namespaced(params)
            groups = weights_mod.plan_chunks(items)
            cache = (version, groups)
            with self._export_lock:
                self._export_cache = cache
        version, groups = cache
        if not 0 <= seq < len(groups):
            raise KeyError(f"weights chunk {seq} beyond plan "
                           f"({len(groups)} chunks at epoch {version})")
        return weights_mod.pack_chunk(groups[seq], version, seq,
                                      len(groups), False)

    def handle_metadata(self, name: str) -> dict:
        if name != self.engine.cfg.model:
            raise KeyError(f"model {name!r} not served")
        meta = self.engine.metadata()
        meta["state"] = "AVAILABLE" if self.engine.ready else "LOADING"
        return meta

    # ------------------------------------------------------------------

    def _make_handler(server: "ModelServer"):
        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _send(self, code: int, payload: dict | str,
                      content_type="application/json") -> None:
                body = (
                    payload if isinstance(payload, str)
                    else json.dumps(payload)
                ).encode()
                self.send_response(code)
                rid = getattr(self, "_request_id", None)
                if rid:
                    self.send_header(REQUEST_ID_HEADER, rid)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path in ("/healthz", "/livez"):
                    # "warming" is alive-but-not-serving: the gateway
                    # route-excludes without a failure-counter penalty
                    # (a newborn mid-compile is not a dead upstream).
                    if server.warm_error is not None:
                        self._send(500, {"status": "failed",
                                         "error": server.warm_error})
                    else:
                        status = "warming" if server.warming else "ok"
                        self._send(200, {"status": status})
                elif self.path == "/readyz":
                    code = 200 if server.engine.ready else 503
                    self._send(code, {"ready": server.engine.ready})
                elif self.path == "/monitoring/prometheus/metrics":
                    text = server.metrics.render()
                    if server._decoder is not None:
                        # One renderer for every exporter: the decoder's
                        # registry carries the latency histograms
                        # (TTFT, inter-token, dispatch, queue wait,
                        # occupancy); the dict below maps its counter
                        # snapshot (counters by _total suffix, gauges
                        # otherwise).
                        d = server._decoder.metrics()
                        text += server._decoder.registry.render()
                        text += render_prometheus({
                            "serving_decode_steps_total": d["decode_steps"],
                            "serving_decode_dispatches_total":
                                d["decode_dispatches"],
                            "serving_prefill_dispatches_total":
                                d["prefill_dispatches"],
                            "serving_prefill_tokens_total":
                                d["prefill_tokens"],
                            "serving_requests_admitted_total":
                                d["requests_admitted"],
                            "serving_tokens_emitted_total":
                                d["tokens_emitted"],
                            "serving_ttft_avg_seconds": d["ttft_avg_s"],
                            "serving_prefix_hits_total": d["prefix_hits"],
                            "serving_prefix_misses_total":
                                d["prefix_misses"],
                            "serving_prefix_evictions_total":
                                d["prefix_evictions"],
                            "serving_prefix_tokens_reused_total":
                                d["prefix_tokens_reused"],
                            "serving_prefix_suffix_tokens_total":
                                d["prefix_suffix_tokens"],
                            "serving_prefix_entries": d["prefix_entries"],
                            "serving_spec_drafted_tokens_total":
                                d["spec_drafted_tokens"],
                            "serving_spec_accepted_tokens_total":
                                d["spec_accepted_tokens"],
                            "serving_spec_verify_dispatches_total":
                                d["spec_verify_dispatches"],
                            "serving_spec_draft_dispatches_total":
                                d["spec_draft_dispatches"],
                            "serving_spec_acceptance_rate":
                                d["spec_acceptance_rate"],
                            # Per-row state that is not K/V and what
                            # the block selection read (presets with
                            # mixer_types; 0 otherwise).
                            "serving_state_bytes": d["state_bytes"],
                            "serving_sparse_attn_pallas":
                                int(d["sparse_attn_impl"] == "pallas"),
                            "serving_dense_attn_pallas":
                                int(d["dense_attn_impl"] == "pallas"),
                            "serving_sparse_tokens_attended_total":
                                d["sparse_tokens_attended"],
                            "serving_sparse_tokens_in_context_total":
                                d["sparse_tokens_in_context"],
                            "serving_rows_dense_total": d["rows_dense"],
                            "serving_rows_sparse_total": d["rows_sparse"],
                            # K/V is held per CACHE layer (a looped
                            # stack: n_layers x n_passes), which is what
                            # serving_kv_bytes_per_token prices; the
                            # weights beside it are n_layers' alone.
                            "serving_cache_layers": d["cache_layers"],
                            "serving_loop_passes_total": d["loop_passes"],
                            "serving_kv_tokens_attended_total":
                                d["kv_tokens_attended"],
                            "serving_kv_blocks_total": d["kv_blocks_total"],
                            "serving_kv_blocks_in_use":
                                d["kv_blocks_in_use"],
                            # Real-byte gauges for the autoscaler:
                            # block counts shift meaning with kv_dtype,
                            # bytes do not.
                            "serving_kv_bytes_per_token":
                                d["kv_bytes_per_token"],
                            "serving_kv_bytes_in_use":
                                d["kv_bytes_in_use"],
                            "serving_kv_bytes_total":
                                d["kv_bytes_total"],
                            "serving_kv_dtype_int8":
                                int(d["kv_dtype"] == "int8"),
                            "serving_kv_cow_copies_total":
                                d["kv_cow_copies"],
                            "serving_kv_shared_blocks_total":
                                d["kv_shared_blocks"],
                            "serving_kv_defer_admissions_total":
                                d["kv_defer_admissions"],
                            # Disaggregated handoff counters (the role
                            # itself rides the serving_role gauge on
                            # the decoder registry above).
                            "serving_kv_handoff_exports_total":
                                d["kv_handoff_exports"],
                            "serving_kv_handoff_imports_total":
                                d["kv_handoff_imports"],
                            "serving_kv_handoff_tokens_total":
                                d["kv_handoff_tokens"],
                            # Tiered KV (HBM -> host) + QoS: tier
                            # occupancy gauges (pinned = suspended
                            # streams' parked payloads) and the
                            # suspend/resume/shed counters.
                            "serving_kv_host_tier_bytes":
                                d["kv_host_tier_bytes"],
                            "serving_kv_host_tier_bytes_total":
                                d["kv_host_tier_bytes_total"],
                            "serving_kv_host_tier_pinned_bytes":
                                d["kv_host_tier_pinned_bytes"],
                            "serving_kv_host_tier_entries":
                                d["kv_host_tier_entries"],
                            "serving_kv_host_demotions_total":
                                d["kv_host_demotions"],
                            "serving_kv_host_promotions_total":
                                d["kv_host_promotions"],
                            "serving_kv_host_evictions_total":
                                d["kv_host_evictions"],
                            # High-water occupancy (sizing signal for
                            # the host tier and the cold store under
                            # it; the eviction-age histogram rides the
                            # decoder registry above).
                            "serving_kv_host_tier_high_water_bytes":
                                d["kv_host_tier_high_water_bytes"],
                            # Fleet KV economy (peer + cold tiers):
                            # hit/miss/bytes per remote tier, the
                            # staleness refusals that prove mid-pull
                            # weight pushes degrade safely, and the
                            # crossover skips (remote KV existed but
                            # the gain was below the import threshold).
                            "serving_kv_peer_hits_total":
                                d["kv_peer_hits"],
                            "serving_kv_peer_misses_total":
                                d["kv_peer_misses"],
                            "serving_kv_peer_import_bytes_total":
                                d["kv_peer_import_bytes"],
                            "serving_kv_peer_fetch_failures_total":
                                d["kv_peer_fetch_failures"],
                            "serving_kv_cold_hits_total":
                                d["kv_cold_hits"],
                            "serving_kv_cold_demotions_total":
                                d["kv_cold_demotions"],
                            "serving_kv_cold_import_bytes_total":
                                d["kv_cold_import_bytes"],
                            "serving_kv_import_stale_refused_total":
                                d["kv_import_stale_refused"],
                            "serving_kv_import_skipped_crossover_total":
                                d["kv_import_skipped_crossover"],
                            "serving_kv_directory_publishes_total":
                                d["kv_directory_publishes"],
                            # Shared-tier gauges, present only when the
                            # replica carries the economy objects.
                            **{f"serving_{k}": d[k] for k in (
                                "kv_cold_store_bytes",
                                "kv_cold_store_bytes_total",
                                "kv_cold_store_entries",
                                "kv_directory_keys") if k in d},
                            "serving_suspends_total": d["kv_suspends"],
                            "serving_resumes_total": d["kv_resumes"],
                            "serving_deadline_shed_total":
                                d["qos_deadline_shed"],
                            "serving_hol_bypasses_total":
                                d["hol_bypasses"],
                            "serving_qos_enabled":
                                int(d["qos_enabled"]),
                            # Live weight streaming: the version gauge,
                            # push counter and push-seconds histogram
                            # ride the decoder registry above; the
                            # stale-hit refusals land here.
                            "serving_weights_stale_refused_total":
                                d["weights_stale_refused"],
                            # Flash-crowd birth surface: persistent
                            # compile-cache coverage of the dispatch
                            # set, and the ramp gate (1 while this
                            # replica is spill-only).
                            "serving_compile_cache_hits_total":
                                d["compile_cache_hits"],
                            "serving_compile_cache_misses_total":
                                d["compile_cache_misses"],
                            "serving_warm_failed_shapes":
                                d["warm_failed_shapes"],
                            "serving_warming": int(d["warming"]),
                            "serving_in_flight": d["in_flight"],
                            "serving_queued": d["queued"],
                            # serving_tp_shards rides the decoder
                            # registry above; the kv_bytes gauges here
                            # are PER CHIP under tp (real per-chip HBM).
                        })
                    self._send(200, text, content_type="text/plain")
                elif self.path.partition("?")[0] == "/debug/requests":
                    # One curl away: the decoder's per-stream lifecycle
                    # timelines (JSON; ?format=chrome for a
                    # chrome://tracing file; ?id=<rid> filters).
                    if server._decoder is None:
                        self._send(200, {"open": [], "finished": []})
                    else:
                        body, ctype = render_debug(
                            server._decoder.trace,
                            self.path.partition("?")[2])
                        self._send(200, body.decode(), content_type=ctype)
                elif self.path.partition("?")[0] == "/debug/rounds":
                    # The scheduler's own record, a round each: the newest
                    # rounds and the newest slow ones (?slow=1 for those
                    # alone; ?format=chrome for a trace-event file).
                    if server._decoder is None:
                        self._send(200, {"rounds": [], "slow": []})
                    else:
                        body, ctype = render_rounds(
                            server._decoder.rounds,
                            self.path.partition("?")[2])
                        self._send(200, body.decode(), content_type=ctype)
                elif self.path.startswith("/v1/models/"):
                    name = self.path[len("/v1/models/"):]
                    try:
                        self._send(200, server.handle_metadata(name))
                    except KeyError as e:
                        self._send(404, {"error": str(e)})
                else:
                    self._send(404, {"error": f"no route {self.path}"})

            # Chunked transfer-encoding requires HTTP/1.1 on the status
            # line — the BaseHTTPRequestHandler default is HTTP/1.0, under
            # which spec-compliant clients would read the chunk framing as
            # payload.
            protocol_version = "HTTP/1.1"

            def _chunk(self, rec: dict) -> None:
                data = (json.dumps(rec) + "\n").encode()
                self.wfile.write(f"{len(data):x}\r\n".encode())
                self.wfile.write(data + b"\r\n")
                self.wfile.flush()

            def _send_stream(self, records) -> None:
                """Chunked transfer-encoding, one JSON line per record —
                each token flushes to the client as it is sampled (the
                gateway's streamed proxying passes chunks through). Once
                the 200 goes out this owns the connection: a mid-stream
                decoder failure becomes an error record + clean terminal
                chunk, never a second status line."""
                self.send_response(200)
                rid = getattr(self, "_request_id", None)
                if rid:
                    self.send_header(REQUEST_ID_HEADER, rid)
                self.send_header("Content-Type", "application/jsonlines")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                try:
                    for rec in records:
                        self._chunk(rec)
                except Exception as e:
                    self._chunk({"error": str(e), "done": True})
                finally:
                    self.wfile.write(b"0\r\n\r\n")
                    self.wfile.flush()

            def _qos_headers(self) -> dict:
                """The QoS surface threaded from the gateway: tenant
                identity, request priority, and a shed deadline. Bad
                numeric values are a client error (400 via the
                ValueError path), not a silent default."""
                qos = {}
                tenant = self.headers.get("X-Tenant")
                if tenant:
                    qos["tenant"] = tenant
                prio = self.headers.get("X-Priority")
                if prio:
                    try:
                        qos["priority"] = int(prio)
                    except ValueError:
                        raise ValueError(
                            f"malformed X-Priority {prio!r}") from None
                deadline = self.headers.get("X-Deadline-Ms")
                if deadline:
                    try:
                        qos["deadline_ms"] = float(deadline)
                    except ValueError:
                        raise ValueError(
                            f"malformed X-Deadline-Ms {deadline!r}"
                        ) from None
                return qos

            def do_POST(self):
                t0 = time.perf_counter()
                error = False
                # Request id: honor the gateway's (or the client's),
                # mint one otherwise; echoed on every response and keyed
                # into the decoder's timeline for this stream.
                self._request_id = (self.headers.get(REQUEST_ID_HEADER)
                                    or gen_request_id())
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(length) or b"{}")
                    if self.path.startswith("/v1/models/") and \
                            self.path.endswith(":predict"):
                        name = self.path[len("/v1/models/"):-len(":predict")]
                        qos = self._qos_headers()
                        if body.get("stream"):
                            self._send_stream(
                                server.handle_predict_stream(
                                    name, body,
                                    request_id=self._request_id,
                                    qos=qos)
                            )
                        else:
                            self._send(200, server.handle_predict(
                                name, body,
                                request_id=self._request_id, qos=qos))
                    elif self.path.startswith("/v1/models/") and \
                            self.path.endswith(":prefill"):
                        name = self.path[len("/v1/models/"):-len(":prefill")]
                        self._send(200, server.handle_prefill(
                            name, body, request_id=self._request_id))
                    elif self.path.startswith("/v1/models/") and \
                            self.path.endswith(":import"):
                        name = self.path[len("/v1/models/"):-len(":import")]
                        self._send(200, server.handle_import(name, body))
                    elif self.path.startswith("/v1/models/") and \
                            self.path.endswith(":kv"):
                        name = self.path[len("/v1/models/"):-len(":kv")]
                        self._send(200, server.handle_kv(name, body))
                    elif self.path.startswith("/v1/models/") and \
                            self.path.endswith(":weights"):
                        name = self.path[len("/v1/models/"):
                                         -len(":weights")]
                        self._send(200, server.handle_weights(name, body))
                    elif self.path.startswith("/v1/models/") and \
                            self.path.endswith(":pull"):
                        name = self.path[len("/v1/models/"):-len(":pull")]
                        self._send(200,
                                   server.handle_weights_pull(name, body))
                    else:
                        error = True
                        self._send(404, {"error": f"no route {self.path}"})
                except KeyError as e:
                    error = True
                    self._send(404, {"error": str(e)})
                except QosRejected as e:
                    # Token-bucket overload: shed with backpressure the
                    # client can act on instead of queuing into
                    # collapse.
                    error = True
                    self.send_response(429)
                    rid = getattr(self, "_request_id", None)
                    if rid:
                        self.send_header(REQUEST_ID_HEADER, rid)
                    payload = json.dumps({"error": str(e)}).encode()
                    self.send_header("Retry-After",
                                     str(max(1, int(e.retry_after_s
                                                    + 0.999))))
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                except TimeoutError as e:
                    # An overloaded/stalled decoder is a server-side
                    # failure, not a bad request (deadline sheds — a
                    # DeadlineExceeded is a TimeoutError — land here
                    # too: the answer's window has passed).
                    error = True
                    self._send(503, {"error": str(e) or "generation "
                                     "timed out"})
                except PromptTooLong as e:
                    # Terminal size rejection (prompt beyond the
                    # replica's ceiling even chunked) — 413 so clients
                    # can tell "shrink the prompt" from 400's "fix the
                    # request" and from memory-pressure 503s. Ordered
                    # before ValueError: PromptTooLong subclasses it.
                    error = True
                    self._send(413, {"error": str(e)})
                except ValueError as e:
                    error = True
                    self._send(400, {"error": str(e)})
                except Exception as e:
                    error = True
                    self._send(500, {"error": str(e)})
                finally:
                    server.metrics.observe(time.perf_counter() - t0, error)

        return Handler

    def _start_grpc(self) -> None:
        if self.grpc_port is None:
            return
        from kubeflow_tpu.serving.grpc_server import GrpcPredictionService

        self._grpc = GrpcPredictionService(self, port=self.grpc_port)
        self.grpc_port = self._grpc.bound_port  # resolve port 0 → real port
        self._grpc.start()

    def warm(self) -> None:
        """Boot warm path, run AFTER the HTTP port binds so ``/healthz``
        answers ``warming`` (route-excluded, not dead) for the whole
        birth instead of connection-refusing: the engine's tree relaid
        as the decoder will hold it; when the flash-crowd surface is
        configured (``compile_cache_dir``/``weight_peers``) an eager
        decoder build; then engine warmup (compiles the predict
        executable, for the tree it will keep serving, however late the
        decoder is built), then the decoder's dispatch-set warm, so the
        replica joins the fleet with nothing left to compile. Publishes
        the per-phase cold-start breakdown and flips ``warming`` off.
        Raises when the predict compile fails or the decoder reports a
        dispatch shape that did not warm: a replica that cannot compile
        its own dispatch set must not join the fleet looking healthy."""
        t0 = time.perf_counter()
        eager = (self.engine.cfg.compile_cache_dir
                 or self.engine.cfg.weight_peers)
        # The relay first: the predict executable compiles for the tree
        # the decoder will share, eager or not.
        if self._generates:
            self.engine.relay_for_decoder()
        decoder = self.decoder if eager else None
        self.engine.warmup()
        if decoder is not None:
            decoder.warming = True
            report = decoder.warm()
            if report["failed"]:
                raise RuntimeError(
                    f"decoder warm failed for {report['failed']} "
                    f"dispatch shape(s) {report['failed_shapes']}: "
                    f"{report['first_error']}")
        self.engine.cold_start["compile"] = time.perf_counter() - t0
        self.engine.cold_start["first_token"] = (time.perf_counter()
                                                 - self._t_boot)
        self.metrics.record_cold_start(self.engine.cold_start)
        self.metrics.record_weight_pull(self.engine.weight_pull_source)
        self.warming = False

    def start(self) -> None:
        self._start_grpc()
        self._httpd = ThreadingHTTPServer(
            ("0.0.0.0", self.port), self._make_handler()
        )
        self.port = self._httpd.server_address[1]
        thread = threading.Thread(target=self._httpd.serve_forever,
                                  daemon=True)
        thread.start()
        self.warm()

    def serve_forever(self) -> None:
        self._start_grpc()
        self._httpd = ThreadingHTTPServer(
            ("0.0.0.0", self.port), self._make_handler()
        )
        # Warm on a side thread: the accept loop must answer health
        # probes (as "warming") while the dispatch set compiles. A warm
        # that raises is recorded for /healthz and stops the accept
        # loop, so the failure ends the process instead of leaving a
        # server that answers "warming" forever.
        def _warm():
            try:
                self.warm()
            except Exception as e:  # boundary: reported below, re-raised
                import traceback

                traceback.print_exc()
                self.warm_error = f"{type(e).__name__}: {e}"
                self._httpd.shutdown()

        threading.Thread(target=_warm, daemon=True).start()
        self._httpd.serve_forever()
        if self.warm_error is not None:
            raise RuntimeError(f"server warm failed: {self.warm_error}")

    def stop(self) -> None:
        if self._httpd:
            self._httpd.shutdown()
        if self._grpc is not None:
            self._grpc.stop()
        self.batcher.stop()
        with self._decoder_lock:
            if self._decoder is not None:
                self._decoder.stop()
