"""CLI: `python -m kubeflow_tpu.serving --model-name ... --rest-port 8500`.

The container entrypoint the tpu-serving manifest runs
(kubeflow_tpu/manifests/packages/serving.py args)."""

from __future__ import annotations

import argparse
import signal
import threading

from kubeflow_tpu.serving.engine import EngineConfig
from kubeflow_tpu.serving.server import ModelServer
from kubeflow_tpu.utils.jaxenv import device_line, place_compile_cache


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model-name", required=True,
                   help="registry model name (kubeflow_tpu.models)")
    p.add_argument("--model-path", default="",
                   help="checkpoint dir (empty = fresh init, benchmarking)")
    p.add_argument("--rest-port", type=int, default=8500)
    p.add_argument("--grpc-port", type=int, default=9000,
                   help="gRPC predict port (tf-serving :9000 contract); "
                        "-1 disables")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--batch-timeout-ms", type=float, default=5.0)
    p.add_argument("--max-seq-len", type=int, default=128)
    p.add_argument("--max-new-tokens", type=int, default=16,
                   help="per-request generation cap (0 disables the "
                        "decode path entirely)")
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--eos-id", type=int, default=-1,
                   help="token id ending a generation early; -1 disables")
    p.add_argument("--decode-mode", default="continuous",
                   choices=["continuous", "lockstep"],
                   help="continuous: per-request lengths decoupled + "
                        "streaming; lockstep: one compiled call per batch")
    p.add_argument("--decode-chunk", type=int, default=1,
                   help="decode steps fused per device dispatch in "
                        "continuous mode: K>1 makes K times fewer "
                        "dispatches, and a new request or a streamed "
                        "token waits up to K steps")
    p.add_argument("--prefix-cache-slots", type=int, default=0,
                   help="device prefix-KV pool slots for reuse of shared "
                        "prompt prefixes (0 disables); matching prompts "
                        "prefill only their suffix")
    p.add_argument("--prefix-cache-min-len", type=int, default=16,
                   help="shortest prefix worth caching/matching")
    p.add_argument("--prefill-len-buckets", type=int, default=0,
                   help="power-of-two prefill length buckets below "
                        "max-seq-len (0 = pad every prompt to "
                        "max-seq-len)")
    p.add_argument("--speculative-k", type=int, default=0,
                   help="draft tokens verified per fused decode dispatch "
                        "(0 disables speculative decoding); greedy "
                        "outputs are unchanged, throughput multiplies "
                        "with the acceptance rate")
    p.add_argument("--draft-mode", default="ngram",
                   help="speculative draft proposer: 'ngram' (host-side "
                        "prompt/output lookup, zero device cost) or "
                        "'model:<registry-name>' (small draft model)")
    p.add_argument("--kv-layout", default="dense",
                   choices=["dense", "paged"],
                   help="continuous-mode KV layout: 'dense' reserves a "
                        "full-length row per decode slot; 'paged' backs "
                        "requests with fixed-size blocks from a shared "
                        "pool — admission bounded by memory, zero-copy "
                        "prefix sharing, byte-identical greedy outputs")
    p.add_argument("--kv-block-size", type=int, default=16,
                   help="tokens per KV block (paged layout); must divide "
                        "max-seq-len + max-new-tokens")
    p.add_argument("--kv-pool-blocks", type=int, default=0,
                   help="physical blocks in the paged pool (0 = "
                        "dense-parity sizing: batch-size sequences at "
                        "worst case)")
    p.add_argument("--kv-dtype", default="fp", choices=["fp", "int8"],
                   help="paged KV residency precision: 'fp' keeps the "
                        "model dtype (bitwise-parity default); 'int8' "
                        "quantizes blocks with per-position per-head "
                        "scales — ~2x blocks per HBM byte within a "
                        "pinned greedy-token tolerance")
    p.add_argument("--kv-fused-attention", action="store_true",
                   help="fuse the paged decode read into a block-table "
                        "attention kernel (no dense KV gather per step; "
                        "int8 dequantized in-register); numerics are "
                        "f32-equivalent, not bitwise")
    p.add_argument("--serving-role", default="",
                   choices=["", "prefill", "decode"],
                   help="disaggregated-fleet role: 'prefill' runs "
                        "prompt admission only (decode peers pull "
                        "finished prompt KV via :prefill/:import), "
                        "'decode' resumes imported prompts; empty = "
                        "colocated. Requires --kv-layout=paged")
    p.add_argument("--tp-shards", type=int, default=1,
                   help="tensor-parallel shards per replica (continuous "
                        "mode): >1 runs the decoder over a tp-wide "
                        "tensor mesh — weights Megatron-split, the KV "
                        "pool sharded over the KV-head axis; must "
                        "divide the model's kv heads / heads / d_ff "
                        "and the pod needs that many chips")
    p.add_argument("--prefill-chunk-tokens", type=int, default=0,
                   help="chunked prefill (continuous mode, paged "
                        "layout): admit prompts longer than this as a "
                        "chain of bounded chunk dispatches interleaved "
                        "with decode rounds, so a long admission never "
                        "stalls live streams for more than one chunk "
                        "of prefill compute; 0 disables (monolithic "
                        "admission). Token streams stay byte-identical")
    p.add_argument("--max-prompt-len", type=int, default=0,
                   help="prompt-length ceiling (0 = max-seq-len). "
                        "Raising it past max-seq-len requires "
                        "--prefill-chunk-tokens: chunks ride the paged "
                        "block scatter, so only the KV row bounds the "
                        "prompt. Longer prompts are rejected with 413, "
                        "never truncated")
    p.add_argument("--cp-shards", type=int, default=1,
                   help="context-parallel shards: >1 runs each prefill "
                        "chunk's attention ring-style over a sequence "
                        "mesh axis — long-prompt prefill FLOPs scale "
                        "with cp while decode stays tp-only; requires "
                        "--prefill-chunk-tokens and the paged gather "
                        "path; the pod needs tp*cp*pp chips")
    p.add_argument("--pp-stages", type=int, default=1,
                   help="pipeline-parallel decoder stages: >1 shards "
                        "the layer stack AND the KV pool's layer dim "
                        "over a pipeline mesh axis (per-chip weight + "
                        "KV bytes divide by pp); must divide the "
                        "model's n_layers; the pod needs tp*cp*pp "
                        "chips")
    p.add_argument("--host-kv-bytes", type=int, default=0,
                   help="host-RAM KV tier budget in bytes (paged "
                        "layout; 0 disables): prefix evictions demote "
                        "blocks to host memory instead of freeing, "
                        "misses re-import them (second-chance cache), "
                        "and QoS suspensions park live streams' KV "
                        "there until resume")
    p.add_argument("--kv-directory-size", type=int, default=0,
                   help="fleet KV economy: distinct prefix affinity "
                        "keys the prefix->holder directory tracks "
                        "(paged layout; 0 disables). Local misses "
                        "probe directory hints and pull the deepest "
                        "advertised prefix from the holding peer over "
                        "the :kv handoff endpoint, prefilling only "
                        "the tail")
    p.add_argument("--cold-store-ref", default="",
                   help="shared cold content-addressed KV store "
                        "('mem://<name>[?bytes=<n>]'; empty disables): "
                        "host-tier evictions demote payloads there "
                        "before dropping bytes; the weights epoch "
                        "rides the content key, so a live weight push "
                        "invalidates pre-swap blobs by construction")
    p.add_argument("--kv-import-crossover-tokens", type=int, default=0,
                   help="minimum prefill tokens a peer/cold import "
                        "must save over the best local tier before "
                        "the pull is worth its fixed cost; 0 imports "
                        "any strictly deeper match")
    p.add_argument("--qos-tenants", default="",
                   help="multi-tenant QoS spec: 'name=weight[:rate"
                        "[:burst[:priority]]]' comma-separated (empty "
                        "disables QoS); requests carry X-Tenant/"
                        "X-Priority/X-Deadline-Ms headers, buckets "
                        "answer 429 + Retry-After, the pop loop "
                        "orders by weighted fair share + aged "
                        "priority")
    p.add_argument("--qos-aging-s", type=float, default=30.0,
                   help="seconds of queue wait worth one priority "
                        "point (starvation aging; <=0 disables)")
    p.add_argument("--compile-cache-dir", default="",
                   help="persistent compile-cache directory (empty "
                        "disables): a newborn replica replays the "
                        "fingerprint-matched serialized executables "
                        "for its whole decode dispatch set instead of "
                        "cold-compiling it, and records its own "
                        "compiles for the next birth")
    p.add_argument("--weight-peers", default="",
                   help="comma-separated host:port donors to pull the "
                        "boot weights from over :pull (tried in "
                        "order, checkpoint fallback; empty boots from "
                        "the checkpoint)")
    p.add_argument("--weight-pull-timeout-s", type=float, default=30.0,
                   help="per-donor budget for the boot-time weight "
                        "pull before trying the next donor")
    p.add_argument("--stream-timeout-s", type=float, default=60.0,
                   help="default wait for generation results/streams; "
                        "raise under heavy load so memory-deferred "
                        "admissions don't time callers out")
    p.add_argument("--dtype", default="",
                   choices=["", "bfloat16", "float32"],
                   help="compute dtype override; empty keeps the model "
                        "preset's dtype")
    # Metrics are always served at /monitoring/prometheus/metrics; the
    # flag exists so the rendered manifest args stay valid
    # (tf-serving-template.libsonnet enablePrometheus parity).
    p.add_argument("--enable-prometheus", action="store_true")
    args = p.parse_args(argv)
    if args.eos_id >= 0 and args.decode_mode != "continuous":
        # Only the continuous decoder implements early stop; silently
        # generating past EOS would return post-EOS garbage.
        p.error("--eos-id requires --decode-mode=continuous")
    if args.prefix_cache_slots > 0 and args.decode_mode != "continuous":
        # Only the continuous decoder carries the prefix pool; silently
        # ignoring the flag would report cache-off numbers as cache-on.
        p.error("--prefix-cache-slots requires --decode-mode=continuous")
    if args.speculative_k > 0 and args.decode_mode != "continuous":
        # Verification rides the continuous decode state; silently
        # ignoring the flag would report plain-decode numbers as
        # speculative ones.
        p.error("--speculative-k requires --decode-mode=continuous")
    if not (args.draft_mode == "ngram"
            or args.draft_mode.startswith("model:")):
        p.error("--draft-mode must be 'ngram' or 'model:<name>'")
    if args.kv_dtype != "fp" and args.kv_layout != "paged":
        # Quantized residency exists only in the block pool; silently
        # ignoring the flag would report fp memory numbers as int8 ones.
        p.error("--kv-dtype=int8 requires --kv-layout=paged")
    if args.kv_fused_attention and args.kv_layout != "paged":
        # The fused kernel reads through the block table; dense rows
        # have no table to walk.
        p.error("--kv-fused-attention requires --kv-layout=paged")
    if args.serving_role and args.kv_layout != "paged":
        # The prefill→decode handoff rides the paged block pool; a
        # dense replica has no blocks to export or import.
        p.error("--serving-role requires --kv-layout=paged")
    if args.tp_shards < 1:
        p.error("--tp-shards must be >= 1")
    if args.cp_shards < 1:
        p.error("--cp-shards must be >= 1")
    if args.pp_stages < 1:
        p.error("--pp-stages must be >= 1")
    if args.prefill_chunk_tokens < 0:
        p.error("--prefill-chunk-tokens must be >= 0")
    if args.max_prompt_len < 0:
        p.error("--max-prompt-len must be >= 0")
    if args.prefill_chunk_tokens and args.kv_layout != "paged":
        # Chunks scatter through the block table; dense rows have no
        # table to scatter through.
        p.error("--prefill-chunk-tokens requires --kv-layout=paged")
    if (args.max_prompt_len > args.max_seq_len
            and not args.prefill_chunk_tokens):
        # Monolithic prefill is bounded by the compiled width; silently
        # accepting the flag would 413 every long prompt anyway.
        p.error("--max-prompt-len beyond max-seq-len requires "
                "--prefill-chunk-tokens")
    if args.cp_shards > 1 and not args.prefill_chunk_tokens:
        # The sequence axis only carries chunked-prefill attention;
        # silently ignoring the flag would report tp-only numbers as
        # context-parallel ones.
        p.error("--cp-shards requires --prefill-chunk-tokens")
    if args.cp_shards > 1 and args.kv_fused_attention:
        p.error("--cp-shards uses the gathered ring read; drop "
                "--kv-fused-attention")
    if args.pp_stages > 1 and args.decode_mode != "continuous":
        p.error("--pp-stages requires --decode-mode=continuous")
    if args.host_kv_bytes < 0:
        p.error("--host-kv-bytes must be >= 0")
    if args.host_kv_bytes and args.kv_layout != "paged":
        # The tier stores exported BLOCK payloads; dense rows have no
        # blocks to demote or re-import.
        p.error("--host-kv-bytes requires --kv-layout=paged")
    if args.kv_directory_size < 0:
        p.error("--kv-directory-size must be >= 0")
    if args.kv_import_crossover_tokens < 0:
        p.error("--kv-import-crossover-tokens must be >= 0")
    if ((args.kv_directory_size or args.cold_store_ref)
            and args.kv_layout != "paged"):
        # The economy imports land through the paged scatter; dense
        # rows have no block pool to install a pulled prefix into.
        p.error("--kv-directory-size/--cold-store-ref require "
                "--kv-layout=paged")
    if args.cold_store_ref:
        from kubeflow_tpu.serving.cold_store import cold_store_from_ref

        try:
            cold_store_from_ref(args.cold_store_ref)
        except ValueError as e:
            # A typo'd store URL must fail the rollout at flag-parse
            # time, not serve silently without its cold tier.
            p.error(f"--cold-store-ref: {e}")
    if args.qos_tenants:
        if args.decode_mode != "continuous":
            # QoS ordering lives in the continuous pop loop; silently
            # ignoring the flag would serve FIFO while the operator
            # believes fair-share is on.
            p.error("--qos-tenants requires --decode-mode=continuous")
        from kubeflow_tpu.serving.qos import parse_tenants

        try:
            parse_tenants(args.qos_tenants)
        except ValueError as e:
            p.error(f"--qos-tenants: {e}")
    if args.tp_shards > 1 and args.decode_mode != "continuous":
        # Only the continuous decoder builds the tensor mesh; silently
        # ignoring the flag would report single-chip numbers as
        # model-parallel ones.
        p.error("--tp-shards requires --decode-mode=continuous")
    if args.kv_layout == "paged":
        if args.decode_mode != "continuous":
            # Only the continuous decoder carries the block pool;
            # silently ignoring the flag would report dense numbers as
            # paged ones.
            p.error("--kv-layout=paged requires --decode-mode=continuous")
        if args.kv_block_size <= 0:
            p.error("--kv-block-size must be positive")
        total = ((args.max_prompt_len or args.max_seq_len)
                 + args.max_new_tokens)
        if total % args.kv_block_size:
            # Fail at flag-parse time, not at the first generation
            # request (the decoder is built lazily).
            p.error(f"--kv-block-size {args.kv_block_size} must divide "
                    f"max-prompt-len + max-new-tokens = {total}")

    # Before the first compile: where XLA keeps executables, and which
    # device this process is really on.
    print(f"compile cache: {place_compile_cache()}")
    print(device_line(), flush=True)
    server = ModelServer(
        EngineConfig(
            model=args.model_name,
            checkpoint_dir=args.model_path or None,
            batch_size=args.batch_size,
            max_seq_len=args.max_seq_len,
            max_new_tokens=args.max_new_tokens,
            top_k=args.top_k,
            eos_id=None if args.eos_id < 0 else args.eos_id,
            decode_mode=args.decode_mode,
            decode_chunk=args.decode_chunk,
            prefix_cache_slots=args.prefix_cache_slots,
            prefix_cache_min_len=args.prefix_cache_min_len,
            prefill_len_buckets=args.prefill_len_buckets,
            speculative_k=args.speculative_k,
            draft_mode=args.draft_mode,
            kv_layout=args.kv_layout,
            kv_block_size=args.kv_block_size,
            kv_pool_blocks=args.kv_pool_blocks,
            kv_dtype=args.kv_dtype,
            kv_fused=args.kv_fused_attention,
            stream_timeout_s=args.stream_timeout_s,
            serving_role=args.serving_role,
            tp_shards=args.tp_shards,
            prefill_chunk_tokens=args.prefill_chunk_tokens,
            max_prompt_len=args.max_prompt_len,
            cp_shards=args.cp_shards,
            pp_stages=args.pp_stages,
            host_kv_bytes=args.host_kv_bytes,
            kv_directory_size=args.kv_directory_size,
            cold_store_ref=args.cold_store_ref,
            kv_import_crossover_tokens=args.kv_import_crossover_tokens,
            qos_tenants=args.qos_tenants,
            qos_aging_s=args.qos_aging_s,
            weight_peers=args.weight_peers,
            weight_pull_timeout_s=args.weight_pull_timeout_s,
            compile_cache_dir=args.compile_cache_dir,
            dtype=args.dtype,
        ),
        port=args.rest_port,
        grpc_port=None if args.grpc_port < 0 else args.grpc_port,
        batch_timeout_ms=args.batch_timeout_ms,
    )
    print(f"serving {args.model_name} on REST :{args.rest_port} "
          f"gRPC :{args.grpc_port}", flush=True)

    # stop() waits for the accept loop this (main) thread is blocked in,
    # so the handler hands it to another thread; main joins that thread
    # so the decoder and batcher are drained before the process exits.
    stopper = threading.Thread(target=server.stop, daemon=False)

    def _on_signal(_signum, _frame):
        if stopper.ident is None:  # not started yet
            stopper.start()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    server.serve_forever()
    if stopper.ident is not None:
        stopper.join(timeout=30)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
