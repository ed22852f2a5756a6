"""Serving package: TPU model server deployment + service.

The analogue of kubeflow/tf-serving — model-server Deployment with gRPC :9000
and REST :8500 (tf-serving-template.libsonnet:29-49), model loaded from
GCS/S3/PVC (prototypes/tf-serving-gcp.jsonnet:8), TCP liveness probe on the
gRPC port (:70-75), prometheus monitoring (:127-130), gateway/istio routing
(tf-serving-service-template.libsonnet) — with tensorflow/serving replaced by
our TPU inference engine (kubeflow_tpu.serving) and nvidia.com/gpu variants
replaced by google.com/tpu.
"""

from __future__ import annotations

from kubeflow_tpu.apis.jobs import tpu_resources
from kubeflow_tpu.k8s import objects as k8s
from kubeflow_tpu.manifests import images
from kubeflow_tpu.manifests.core import ParamSpec, gateway_route, prototype
from kubeflow_tpu.version import DEFAULT_NAMESPACE

GRPC_PORT = 9000
REST_PORT = 8500


@prototype(
    "tpu-serving",
    "TPU model server Deployment: gRPC :9000 + REST :8500, model from "
    "gs://|s3://|pvc path, prometheus metrics, TPU resources",
    params=[
        ParamSpec("name"),
        ParamSpec("namespace", DEFAULT_NAMESPACE),
        ParamSpec("model_path", "", "gs://, s3://, /pvc/ or local model dir"),
        ParamSpec("model_name", "", "served model name (defaults to `name`)"),
        ParamSpec("image", images.SERVING),
        ParamSpec("replicas", 1),
        ParamSpec("num_tpu_chips", 1, "google.com/tpu chips per replica (0 = CPU)"),
        ParamSpec("batch_size", 8, "max server-side batch size"),
        ParamSpec("batch_timeout_ms", 5, "batching window"),
        ParamSpec("prefix_cache_slots", 0,
                  "device prefix-KV pool slots (0 disables prefix reuse)"),
        ParamSpec("prefix_cache_min_len", 16,
                  "shortest prompt prefix worth caching"),
        ParamSpec("prefill_len_buckets", 0,
                  "power-of-two prefill length buckets below the max "
                  "sequence length (0 = fixed-length prefill)"),
        ParamSpec("speculative_k", 0,
                  "draft tokens verified per fused decode dispatch "
                  "(0 disables speculative decoding)"),
        ParamSpec("draft_mode", "ngram",
                  "speculative draft proposer: ngram or "
                  "model:<registry-name>"),
        ParamSpec("kv_layout", "dense",
                  "KV-cache layout: dense (full-length row per decode "
                  "slot) or paged (block pool; admission by memory, "
                  "zero-copy prefix sharing)"),
        ParamSpec("kv_block_size", 16,
                  "tokens per KV block (paged layout)"),
        ParamSpec("kv_pool_blocks", 0,
                  "physical blocks in the paged pool (0 = dense-parity "
                  "sizing)"),
        ParamSpec("kv_dtype", "fp",
                  "paged KV residency precision: fp (bitwise-parity "
                  "default) or int8 (~2x blocks per HBM byte within a "
                  "pinned greedy tolerance)"),
        ParamSpec("serving_role", "",
                  "disaggregated-fleet role: 'prefill' (prompt "
                  "admission only; decode peers pull finished prompt "
                  "KV via :prefill/:import) or 'decode'; empty = "
                  "colocated. Requires kv_layout=paged"),
        ParamSpec("tp_shards", 1,
                  "tensor-parallel shards per replica: >1 runs the "
                  "decoder over a tp-chip mesh (weights Megatron-"
                  "split, KV pool sharded by KV head); size "
                  "num_tpu_chips to match"),
        ParamSpec("kv_fused_attention", False,
                  "fuse the paged decode read into the block-table "
                  "attention kernel (no dense KV gather per step)"),
        ParamSpec("prefill_chunk_tokens", 0,
                  "chunked prefill: split long admissions into bounded "
                  "chunks interleaved with decode dispatches (0 "
                  "disables; requires kv_layout=paged)"),
        ParamSpec("max_prompt_len", 0,
                  "longest admissible prompt (0 = the prefill window); "
                  "beyond the prefill window requires chunked prefill"),
        ParamSpec("cp_shards", 1,
                  "context-parallel shards for chunk prefill attention "
                  "(>1 rings the span attention across cp chips; "
                  "chips per replica = tp*cp*pp)"),
        ParamSpec("pp_stages", 1,
                  "pipeline-parallel decoder stages (>1 shards stacked "
                  "layers and the KV pool's layer dim across pp "
                  "chips)"),
        ParamSpec("host_kv_bytes", 0,
                  "host-RAM KV tier budget in bytes (paged layout; 0 "
                  "disables): evictions demote blocks to host memory, "
                  "misses re-import them, QoS suspensions park live "
                  "streams' KV there — size the pod's memory request "
                  "to cover it"),
        ParamSpec("kv_directory_size", 0,
                  "fleet KV economy: affinity keys the prefix->holder "
                  "directory tracks (0 disables; requires "
                  "kv_layout=paged). Local misses pull the deepest "
                  "advertised prefix from the holding peer via :kv"),
        ParamSpec("cold_store_ref", "",
                  "shared cold content-addressed KV store "
                  "('mem://<name>[?bytes=<n>]'; empty disables): "
                  "host-tier evictions demote payloads there; the "
                  "weights epoch rides the content key so live pushes "
                  "invalidate by construction"),
        ParamSpec("kv_import_crossover_tokens", 0,
                  "minimum prefill tokens a peer/cold import must save "
                  "over the best local tier before the pull is worth "
                  "its fixed cost (0 = any strictly deeper match)"),
        ParamSpec("qos_tenants", "",
                  "multi-tenant QoS: 'name=weight[:rate[:burst"
                  "[:priority]]]' comma-separated (empty disables); "
                  "requests carry X-Tenant/X-Priority/X-Deadline-Ms"),
        ParamSpec("qos_aging_s", 30.0,
                  "seconds of queue wait worth one priority point "
                  "(starvation aging)"),
        ParamSpec("compile_cache_dir", "",
                  "persistent compile-cache directory (empty disables): "
                  "mounted as a node-shared hostPath so a newborn "
                  "replica replays the fleet's serialized executables "
                  "instead of cold-compiling its dispatch set"),
        ParamSpec("weight_peers", "",
                  "comma-separated host:port donors a newborn pulls its "
                  "weights from over :pull before falling back to the "
                  "checkpoint (empty = checkpoint boot)"),
        ParamSpec("enable_prometheus", True),
        ParamSpec("dtype", "bfloat16"),
    ],
)
def tpu_serving(
    name: str,
    namespace: str,
    model_path: str,
    model_name: str,
    image: str,
    replicas: int,
    num_tpu_chips: int,
    batch_size: int,
    batch_timeout_ms: int,
    prefix_cache_slots: int,
    prefix_cache_min_len: int,
    prefill_len_buckets: int,
    speculative_k: int,
    draft_mode: str,
    kv_layout: str,
    kv_block_size: int,
    kv_pool_blocks: int,
    kv_dtype: str,
    serving_role: str,
    tp_shards: int,
    kv_fused_attention: bool,
    prefill_chunk_tokens: int,
    max_prompt_len: int,
    cp_shards: int,
    pp_stages: int,
    host_kv_bytes: int,
    kv_directory_size: int,
    cold_store_ref: str,
    kv_import_crossover_tokens: int,
    qos_tenants: str,
    qos_aging_s: float,
    compile_cache_dir: str,
    weight_peers: str,
    enable_prometheus: bool,
    dtype: str,
) -> list[dict]:
    model_name = model_name or name
    labels = {"app": name, "service": "tpu-serving"}
    resources = tpu_resources(num_tpu_chips)
    args = [
        f"--model-name={model_name}",
        f"--model-path={model_path}",
        f"--grpc-port={GRPC_PORT}",
        f"--rest-port={REST_PORT}",
        f"--batch-size={batch_size}",
        f"--batch-timeout-ms={batch_timeout_ms}",
        f"--prefix-cache-slots={prefix_cache_slots}",
        f"--prefix-cache-min-len={prefix_cache_min_len}",
        f"--prefill-len-buckets={prefill_len_buckets}",
        f"--speculative-k={speculative_k}",
        f"--draft-mode={draft_mode}",
        f"--kv-layout={kv_layout}",
        f"--kv-block-size={kv_block_size}",
        f"--kv-pool-blocks={kv_pool_blocks}",
        f"--kv-dtype={kv_dtype}",
        f"--tp-shards={tp_shards}",
        f"--dtype={dtype}",
    ]
    if serving_role:
        args.insert(-1, f"--serving-role={serving_role}")
    if kv_fused_attention:
        args.insert(-1, "--kv-fused-attention")
    if prefill_chunk_tokens:
        args.insert(-1, f"--prefill-chunk-tokens={prefill_chunk_tokens}")
    if max_prompt_len:
        args.insert(-1, f"--max-prompt-len={max_prompt_len}")
    if cp_shards > 1:
        args.insert(-1, f"--cp-shards={cp_shards}")
    if pp_stages > 1:
        args.insert(-1, f"--pp-stages={pp_stages}")
    if host_kv_bytes:
        args.insert(-1, f"--host-kv-bytes={host_kv_bytes}")
    if kv_directory_size:
        args.insert(-1, f"--kv-directory-size={kv_directory_size}")
    if cold_store_ref:
        args.insert(-1, f"--cold-store-ref={cold_store_ref}")
    if kv_import_crossover_tokens:
        args.insert(-1, "--kv-import-crossover-tokens="
                    f"{kv_import_crossover_tokens}")
    if qos_tenants:
        args.insert(-1, f"--qos-tenants={qos_tenants}")
        args.insert(-1, f"--qos-aging-s={qos_aging_s}")
    if compile_cache_dir:
        args.insert(-1, f"--compile-cache-dir={compile_cache_dir}")
    if weight_peers:
        args.insert(-1, f"--weight-peers={weight_peers}")
    if enable_prometheus:
        args.append("--enable-prometheus")
    # The compile cache is node-shared state, not pod state: every
    # replica scheduled on the node mounts the same hostPath, so the
    # first compile on the node is the LAST one any sibling pays. The
    # flag places the dispatch-key manifest; XLA's executables follow
    # JAX's own variable (utils/jaxenv.place_compile_cache), which must
    # name the same volume or a manifest hit is booked for an executable
    # this replica compiles again.
    volumes = mounts = env = None
    if compile_cache_dir:
        volumes = [k8s.host_path_volume("compile-cache", compile_cache_dir)]
        mounts = [k8s.volume_mount("compile-cache", compile_cache_dir)]
        env = {"JAX_COMPILATION_CACHE_DIR": f"{compile_cache_dir}/xla"}
    pod_annotations = (
        {
            "prometheus.io/scrape": "true",
            "prometheus.io/path": "/monitoring/prometheus/metrics",
            "prometheus.io/port": str(REST_PORT),
        }
        if enable_prometheus
        else None
    )
    return [
        k8s.deployment(
            name,
            namespace,
            containers=[
                k8s.container(
                    name,
                    image,
                    command=["python", "-m", "kubeflow_tpu.serving"],
                    args=args,
                    env=env,
                    ports={"grpc": GRPC_PORT, "rest": REST_PORT},
                    resources=resources,
                    liveness_probe=k8s.tcp_probe(GRPC_PORT, initial_delay=30),
                    readiness_probe=k8s.http_probe(
                        f"/v1/models/{model_name}", REST_PORT, initial_delay=20
                    ),
                    volume_mounts=mounts,
                )
            ],
            replicas=replicas,
            labels=labels,
            pod_annotations=pod_annotations,
            volumes=volumes,
        ),
        k8s.service(
            name,
            namespace,
            selector=labels,
            ports=[
                {"name": "grpc", "port": GRPC_PORT, "targetPort": GRPC_PORT},
                {"name": "rest", "port": REST_PORT, "targetPort": REST_PORT},
            ],
            labels=labels,
            # Gateway route + service-level scrape annotations: the
            # prometheus service discovery (kubernetes-services job)
            # scrapes replicas through the Service as well, so the
            # decoder's histograms reach the autoscaler even when pod
            # discovery is off.
            annotations={
                **gateway_route(
                    name, f"/models/{name}/",
                    f"{name}.{namespace}:{REST_PORT}"),
                **({"prometheus.io/scrape": "true",
                    "prometheus.io/path":
                        "/monitoring/prometheus/metrics",
                    "prometheus.io/port": str(REST_PORT)}
                   if enable_prometheus else {}),
            },
        ),
    ]


@prototype(
    "batch-predict",
    "Batch prediction Job over a dataset (kubeflow/tf-batch-predict analogue)",
    params=[
        ParamSpec("name"),
        ParamSpec("namespace", DEFAULT_NAMESPACE),
        ParamSpec("model_path"),
        ParamSpec("input_path"),
        ParamSpec("output_path"),
        ParamSpec("image", images.SERVING),
        ParamSpec("num_tpu_chips", 1),
        ParamSpec("batch_size", 64),
    ],
)
def batch_predict(
    name: str,
    namespace: str,
    model_path: str,
    input_path: str,
    output_path: str,
    image: str,
    num_tpu_chips: int,
    batch_size: int,
) -> list[dict]:
    resources = tpu_resources(num_tpu_chips)
    return [
        {
            "apiVersion": "batch/v1",
            "kind": "Job",
            "metadata": k8s.metadata(name, namespace, {"app": name}),
            "spec": {
                "backoffLimit": 2,
                "template": {
                    "metadata": {"labels": {"app": name}},
                    "spec": k8s.pod_spec(
                        [
                            k8s.container(
                                name,
                                image,
                                command=["python", "-m", "kubeflow_tpu.serving.batch_predict"],
                                args=[
                                    f"--model-path={model_path}",
                                    f"--input-path={input_path}",
                                    f"--output-path={output_path}",
                                    f"--batch-size={batch_size}",
                                ],
                                resources=resources,
                            )
                        ],
                        restart_policy="Never",
                    ),
                },
            },
        }
    ]


@prototype(
    "serving-route",
    "Traffic-split route for model serving: weighted A/B or canary "
    "variants plus an optional shadow mirror (the seldon "
    "abtest/mab/shadow prototype surface, kubeflow/seldon/prototypes/"
    "serve-ab-test.jsonnet, core.libsonnet:305)",
    params=[
        ParamSpec("name"),
        ParamSpec("namespace", DEFAULT_NAMESPACE),
        ParamSpec("prefix", None, "route prefix; default /models/<name>/"),
        ParamSpec("primary_service", None,
                  "host:port of the main variant; default <name>.<ns>:8500"),
        ParamSpec("canary_service", "", "host:port of the B/canary variant"),
        ParamSpec("canary_weight", 10,
                  "percent of traffic to the canary (0-100)"),
        ParamSpec("shadow_service", "",
                  "host:port mirrored fire-and-forget"),
        ParamSpec("strategy", "weighted",
                  "weighted (static split) or epsilon-greedy "
                  "(multi-armed bandit over the variants)"),
        ParamSpec("epsilon", 0.1,
                  "bandit exploration rate (epsilon-greedy only)"),
        ParamSpec("outlier_threshold", 0.0,
                  "z-score beyond which a prediction request is tagged "
                  "an outlier (seldon outlier-detector surface); 0 "
                  "disables"),
        ParamSpec("outlier_window", 100,
                  "sliding baseline window for the outlier score"),
    ],
)
def serving_route(
    name: str,
    namespace: str,
    prefix: str | None,
    primary_service: str | None,
    canary_service: str,
    canary_weight: int,
    shadow_service: str,
    strategy: str,
    epsilon: float,
    outlier_threshold: float,
    outlier_window: int,
) -> list[dict]:
    prefix = prefix or f"/models/{name}/"
    primary = primary_service or f"{name}.{namespace}:{REST_PORT}"
    if not 0 <= int(canary_weight) <= 100:
        raise ValueError(f"canary_weight {canary_weight} not in [0, 100]")
    if strategy not in ("weighted", "epsilon-greedy"):
        raise ValueError(f"unknown strategy {strategy!r}")
    if strategy == "epsilon-greedy" and not canary_service:
        # One backend is nothing to explore — the gateway would silently
        # fall back to plain routing while the user believes a bandit runs.
        raise ValueError("epsilon-greedy needs a canary_service variant")
    backends = None
    if canary_service:
        backends = [
            {"service": primary, "weight": 100 - int(canary_weight)},
            {"service": canary_service, "weight": int(canary_weight)},
        ]
    if float(outlier_threshold) < 0:
        raise ValueError("outlier_threshold must be >= 0")
    if float(outlier_threshold) > 0 and int(outlier_window) < 2:
        # The gateway would reject (and silently drop) the whole route at
        # refresh time — fail at render instead.
        raise ValueError("outlier_window must be >= 2")
    route = gateway_route(
        f"{name}-route", prefix, primary,
        backends=backends, shadow=shadow_service or "",
        strategy=strategy if strategy != "weighted" else "",
        epsilon=float(epsilon) if strategy == "epsilon-greedy" else None,
        outlier=({"threshold": float(outlier_threshold),
                  "window": int(outlier_window)}
                 if float(outlier_threshold) > 0 else None),
    )
    # Selector-less carrier Service: exists only to hold the route
    # annotation the gateway discovers (the variants are full Services of
    # their own deployments).
    return [
        k8s.service(
            f"{name}-route", namespace, selector={},
            ports=[{"name": "http", "port": REST_PORT}],
            labels={"app": f"{name}-route"},
            annotations=route,
        )
    ]
