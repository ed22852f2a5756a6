"""InferenceService operator tests: replica reconciliation, the
prefix-affine router Service, and the metric-driven autoscaler e2e
(synthetic breach → scale-up within one reconcile; relief → scale-down
only after cooldown; no flapping across consecutive periods)."""

from __future__ import annotations

import yaml

import pytest

from kubeflow_tpu.apis.inference import (
    inference_service,
    inference_service_crd,
)
from kubeflow_tpu.manifests.core import GATEWAY_ROUTE_ANNOTATION
from kubeflow_tpu.operators.inference import (
    InferenceServiceController,
    REPLICA_LABEL,
    SERVICE_LABEL,
    scrape_signals,
)

NS = "kubeflow"

CALM = {"queue_wait_p99_s": 0.05, "ttft_p99_s": 0.1,
        "kv_utilization": 0.2, "queued": 0.0}
BREACH = {"queue_wait_p99_s": 2.0, "ttft_p99_s": 0.1,
          "kv_utilization": 0.2, "queued": 12.0}
LOW = {"queue_wait_p99_s": 0.01, "ttft_p99_s": 0.01,
       "kv_utilization": 0.05, "queued": 0.0}


@pytest.fixture()
def env(api):
    api.apply(inference_service_crd())
    clock = {"t": 0.0}
    signals = {"value": dict(CALM)}
    scraped = []

    def fetch(addr):
        scraped.append(addr)
        return dict(signals["value"])

    ctrl = InferenceServiceController(api, fetch_metrics=fetch,
                                      clock=lambda: clock["t"])
    return api, ctrl, clock, signals, scraped


def _cr(name="llm", **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("autoscale", {"cooldownSeconds": 30,
                                "scrapePeriodSeconds": 5})
    return inference_service(name, NS, "lm-test-tiny", **kw)


def _status(api, name="llm"):
    return api.get("kubeflow-tpu.org/v1", "InferenceService", name,
                   NS).get("status", {})


def _route(api, name="llm"):
    svc = api.get("v1", "Service", name, NS)
    return yaml.safe_load(
        svc["metadata"]["annotations"][GATEWAY_ROUTE_ANNOTATION])


def test_reconcile_materializes_replicas_and_router(env):
    api, ctrl, _clock, _signals, scraped = env
    api.create(_cr())
    assert ctrl.reconcile_all() == 1

    deps = api.list("apps/v1", "Deployment", NS)
    assert sorted(d["metadata"]["name"] for d in deps) == \
        ["llm-r0", "llm-r1"]
    for d in deps:
        assert d["metadata"]["labels"][SERVICE_LABEL] == "llm"
        assert d["metadata"]["ownerReferences"][0]["kind"] == \
            "InferenceService"
        c = d["spec"]["template"]["spec"]["containers"][0]
        assert "--model-name=lm-test-tiny" in c["args"]
    # Per-replica Services exist (stable rendezvous members) plus the
    # selector-less router Service carrying the prefix-affine route.
    svcs = {s["metadata"]["name"] for s in api.list("v1", "Service", NS)}
    assert {"llm", "llm-r0", "llm-r1"} <= svcs
    route = _route(api)
    assert route["strategy"] == "prefix-affine"
    assert [b["service"] for b in route["backends"]] == \
        ["llm-r0.kubeflow:8500", "llm-r1.kubeflow:8500"]
    assert route["affinity_tokens"] == 32
    assert route["pressure"] == 8
    # Both replicas were scraped.
    assert "llm-r0.kubeflow:8500" in scraped
    st = _status(api)
    assert st["replicas"] == 2
    assert st["scrapedReplicas"] == 2


def test_engine_knobs_flow_into_replica_args(env):
    api, ctrl, *_ = env
    api.create(_cr(name="q", engine={"kv_layout": "paged",
                                     "kv_dtype": "int8",
                                     "speculative_k": 4}))
    ctrl.reconcile_all()
    c = api.get("apps/v1", "Deployment", "q-r0",
                NS)["spec"]["template"]["spec"]["containers"][0]
    assert "--kv-layout=paged" in c["args"]
    assert "--kv-dtype=int8" in c["args"]
    assert "--speculative-k=4" in c["args"]


def test_breach_scales_up_within_one_period_and_rebalances_ring(env):
    api, ctrl, clock, signals, _ = env
    api.create(_cr())
    ctrl.reconcile_all()
    assert _status(api)["replicas"] == 2

    signals["value"] = dict(BREACH)
    clock["t"] += 5
    ctrl.reconcile_all()  # ONE reconcile period after the breach
    st = _status(api)
    assert st["replicas"] == 3
    assert "queue_wait_p99" in st["lastScaleReason"]
    assert st["signals"]["queueWaitP99Ms"] == 2000.0
    # Membership change rewrote the route annotation — the gateway's
    # next refresh rebalances the hash ring over three members.
    assert len(_route(api)["backends"]) == 3
    assert api.get("apps/v1", "Deployment", "llm-r2", NS)


def test_scale_down_waits_for_cooldown_no_flapping(env):
    api, ctrl, clock, signals, _ = env
    api.create(_cr())
    ctrl.reconcile_all()
    signals["value"] = dict(BREACH)
    clock["t"] += 5
    ctrl.reconcile_all()
    assert _status(api)["replicas"] == 3

    # Relief lands immediately but INSIDE the 30s cooldown: three
    # consecutive reconcile periods must not flap the count.
    signals["value"] = dict(LOW)
    for _ in range(3):
        clock["t"] += 5
        ctrl.reconcile_all()
        assert _status(api)["replicas"] == 3
    # Cooldown elapsed → one step down (and the ring shrinks with it).
    clock["t"] += 30
    ctrl.reconcile_all()
    assert _status(api)["replicas"] == 2
    assert len(_route(api)["backends"]) == 2
    assert api.get_or_none("apps/v1", "Deployment", "llm-r2", NS) is None
    assert api.get_or_none("v1", "Service", "llm-r2", NS) is None
    # The next step down needs ANOTHER cooldown.
    clock["t"] += 5
    ctrl.reconcile_all()
    assert _status(api)["replicas"] == 2
    clock["t"] += 30
    ctrl.reconcile_all()
    assert _status(api)["replicas"] == 1  # floor: minReplicas


def test_mid_band_signals_hold_steady(env):
    """Signals over the low-water mark but under the breach target are
    the hysteresis band: no scaling in either direction, ever."""
    api, ctrl, clock, signals, _ = env
    api.create(_cr())
    ctrl.reconcile_all()
    signals["value"] = {"queue_wait_p99_s": 0.35, "ttft_p99_s": 0.5,
                       "kv_utilization": 0.5, "queued": 2.0}
    for _ in range(6):
        clock["t"] += 60
        ctrl.reconcile_all()
        assert _status(api)["replicas"] == 2


def test_max_replicas_caps_scale_up(env):
    api, ctrl, clock, signals, _ = env
    api.create(_cr(replicas=4))
    ctrl.reconcile_all()
    signals["value"] = dict(BREACH)
    for _ in range(3):
        clock["t"] += 5
        ctrl.reconcile_all()
    assert _status(api)["replicas"] == 4


def test_unscrapeable_replicas_never_scale_down(env):
    """No signals (every replica scrape failed) must hold the count —
    scaling down blind would be an outage amplifier."""
    api, ctrl, clock, _signals, _ = env
    ctrl.fetch_metrics = lambda addr: None
    api.create(_cr())
    ctrl.reconcile_all()
    clock["t"] += 120
    ctrl.reconcile_all()
    st = _status(api)
    assert st["replicas"] == 2
    assert st["scrapedReplicas"] == 0


def test_kv_pressure_breach_scales_up(env):
    api, ctrl, clock, signals, _ = env
    api.create(_cr())
    ctrl.reconcile_all()
    signals["value"] = {"queue_wait_p99_s": 0.01, "ttft_p99_s": 0.01,
                       "kv_utilization": 0.95, "queued": 0.0}
    clock["t"] += 5
    ctrl.reconcile_all()
    st = _status(api)
    assert st["replicas"] == 3
    assert "kv_bytes" in st["lastScaleReason"]


def test_deleted_service_cascades_children(env):
    api, ctrl, *_ = env
    api.create(_cr())
    ctrl.reconcile_all()
    assert api.list("apps/v1", "Deployment", NS)
    obj = api.get("kubeflow-tpu.org/v1", "InferenceService", "llm", NS)
    api.delete("kubeflow-tpu.org/v1", "InferenceService", "llm", NS)
    ctrl.reconcile_deleted(obj)
    # ownerReference cascade removed every child.
    assert api.list("apps/v1", "Deployment", NS) == []
    assert all(s["metadata"].get("labels", {}).get(SERVICE_LABEL) != "llm"
               for s in api.list("v1", "Service", NS))
    assert (NS, "llm") not in ctrl._scale_state


def test_replica_label_indices_prune_highest_first(env):
    api, ctrl, clock, signals, _ = env
    api.create(_cr(replicas=3))
    ctrl.reconcile_all()
    labels = {d["metadata"]["name"]:
              d["metadata"]["labels"][REPLICA_LABEL]
              for d in api.list("apps/v1", "Deployment", NS)}
    assert labels == {"llm-r0": "0", "llm-r1": "1", "llm-r2": "2"}
    signals["value"] = dict(LOW)
    clock["t"] += 60
    ctrl.reconcile_all()
    names = sorted(d["metadata"]["name"]
                   for d in api.list("apps/v1", "Deployment", NS))
    assert names == ["llm-r0", "llm-r1"]


# ---------------------------------------------------------------------------
# Disaggregated roles: per-pool reconcile + role-scoped autoscaling
# ---------------------------------------------------------------------------

ROLES = {"prefill": {"replicas": 2, "maxReplicas": 4},
         "decode": {"replicas": 2, "maxReplicas": 4,
                    "engine": {"kv_dtype": "int8"}}}


def _role_cr(name="llm", **kw):
    kw.setdefault("roles", {r: dict(v) for r, v in ROLES.items()})
    kw.setdefault("kv_pressure", 0.85)
    return _cr(name, **kw)


def _role_status(api, name="llm"):
    return _status(api, name).get("roles", {})


def test_role_reconcile_materializes_both_pools_and_router(env):
    api, ctrl, _clock, _signals, scraped = env
    api.create(_role_cr())
    ctrl.reconcile_all()
    deps = {d["metadata"]["name"]:
            d["metadata"]["labels"].get("kubeflow-tpu.org/inference-role")
            for d in api.list("apps/v1", "Deployment", NS)}
    assert deps == {"llm-prefill-r0": "prefill", "llm-prefill-r1":
                    "prefill", "llm-decode-r0": "decode",
                    "llm-decode-r1": "decode"}
    # Role engine overrides land in the replica args, the role itself
    # is pinned, and the handoff's paged layout is forced.
    c = api.get("apps/v1", "Deployment", "llm-prefill-r0",
                NS)["spec"]["template"]["spec"]["containers"][0]
    assert "--serving-role=prefill" in c["args"]
    assert "--kv-layout=paged" in c["args"]
    c = api.get("apps/v1", "Deployment", "llm-decode-r0",
                NS)["spec"]["template"]["spec"]["containers"][0]
    assert "--serving-role=decode" in c["args"]
    assert "--kv-dtype=int8" in c["args"]
    # Router: decode replicas are the predict backends, prefill
    # replicas the two-hop pool, kv_pressure folds into the spill.
    route = _route(api)
    assert [b["service"] for b in route["backends"]] == \
        ["llm-decode-r0.kubeflow:8500", "llm-decode-r1.kubeflow:8500"]
    assert [b["service"] for b in route["prefill_backends"]] == \
        ["llm-prefill-r0.kubeflow:8500", "llm-prefill-r1.kubeflow:8500"]
    assert route["kv_pressure"] == 0.85
    # Both pools were scraped at their own addresses.
    assert "llm-prefill-r0.kubeflow:8500" in scraped
    assert "llm-decode-r1.kubeflow:8500" in scraped
    st = _status(api)
    assert st["replicas"] == 4
    assert st["roles"]["prefill"]["replicas"] == 2
    assert st["roles"]["decode"]["replicas"] == 2


def test_prefill_breach_scales_only_prefill_pool(env):
    """A queue-wait p99 breach is prefill-bound: the prefill pool grows
    by one within one period, the decode pool holds."""
    api, ctrl, clock, signals, _ = env
    api.create(_role_cr())
    ctrl.reconcile_all()
    signals["value"] = dict(BREACH)  # queue_wait over, kv calm
    clock["t"] += 5
    ctrl.reconcile_all()
    roles = _role_status(api)
    assert roles["prefill"]["replicas"] == 3
    assert roles["decode"]["replicas"] == 2
    assert "prefill: scale-up: queue_wait_p99" in \
        _status(api)["lastScaleReason"]
    assert api.get("apps/v1", "Deployment", "llm-prefill-r2", NS)
    assert api.get_or_none("apps/v1", "Deployment", "llm-decode-r2",
                           NS) is None
    # The router's prefill pool grew with it; decode backends held.
    route = _route(api)
    assert len(route["prefill_backends"]) == 3
    assert len(route["backends"]) == 2


def test_decode_kv_breach_scales_only_decode_pool(env):
    """A KV real-byte fill breach is decode-bound: the decode pool
    grows, the prefill pool holds (it keeps no resident KV)."""
    api, ctrl, clock, signals, _ = env
    api.create(_role_cr())
    ctrl.reconcile_all()
    signals["value"] = {"queue_wait_p99_s": 0.01, "ttft_p99_s": 0.01,
                        "inter_token_p99_s": 0.01,
                        "kv_utilization": 0.95, "queued": 0.0}
    clock["t"] += 5
    ctrl.reconcile_all()
    roles = _role_status(api)
    assert roles["decode"]["replicas"] == 3
    assert roles["prefill"]["replicas"] == 2
    assert "decode: scale-up: kv_bytes" in \
        _status(api)["lastScaleReason"]


def test_decode_inter_token_breach_scales_only_decode_pool(env):
    api, ctrl, clock, signals, _ = env
    api.create(_role_cr())
    ctrl.reconcile_all()
    signals["value"] = {"queue_wait_p99_s": 0.01, "ttft_p99_s": 0.01,
                        "inter_token_p99_s": 2.0,
                        "kv_utilization": 0.1, "queued": 0.0}
    clock["t"] += 5
    ctrl.reconcile_all()
    roles = _role_status(api)
    assert roles["decode"]["replicas"] == 3
    assert roles["prefill"]["replicas"] == 2
    assert "inter_token_p99" in _status(api)["lastScaleReason"]


def test_role_cooldown_and_hysteresis_are_per_pool(env):
    """Cooldown/hysteresis semantics are unchanged, per pool: after a
    prefill scale-up, relief scales prefill back down only once ITS
    cooldown elapses — and scaling prefill never blocks a decode
    decision."""
    api, ctrl, clock, signals, _ = env
    api.create(_role_cr())
    ctrl.reconcile_all()
    signals["value"] = dict(BREACH)
    clock["t"] += 5
    ctrl.reconcile_all()
    assert _role_status(api)["prefill"]["replicas"] == 3

    # Relief inside the 30s cooldown: no flap in either pool.
    signals["value"] = dict(LOW)
    for _ in range(3):
        clock["t"] += 5
        ctrl.reconcile_all()
        roles = _role_status(api)
        assert roles["prefill"]["replicas"] == 3
        assert roles["decode"]["replicas"] == 2
    # Cooldown elapsed → prefill steps down; decode (whose own cooldown
    # anchored at first sight) steps down on its own clock.
    clock["t"] += 30
    ctrl.reconcile_all()
    roles = _role_status(api)
    assert roles["prefill"]["replicas"] == 2
    # Per-pool pruning: the highest prefill index went, decode children
    # untouched by that prune.
    assert api.get_or_none("apps/v1", "Deployment", "llm-prefill-r2",
                           NS) is None
    assert api.get("apps/v1", "Deployment", "llm-decode-r0", NS)


def test_role_state_cleared_on_delete(env):
    api, ctrl, *_ = env
    api.create(_role_cr())
    ctrl.reconcile_all()
    assert any(k == (NS, "llm", "prefill") for k in ctrl._scale_state)
    obj = api.get("kubeflow-tpu.org/v1", "InferenceService", "llm", NS)
    api.delete("kubeflow-tpu.org/v1", "InferenceService", "llm", NS)
    ctrl.reconcile_deleted(obj)
    assert not any(k[1] == "llm" for k in ctrl._scale_state)


# ---------------------------------------------------------------------------
# Exposition scraping
# ---------------------------------------------------------------------------


def test_scrape_signals_reads_histograms_and_gauges():
    from kubeflow_tpu.observability.metrics import type_line

    text = "\n".join([
        type_line("serving_queue_wait_seconds", "histogram").strip(),
        'serving_queue_wait_seconds_bucket{le="0.1"} 90',
        'serving_queue_wait_seconds_bucket{le="1.0"} 99',
        'serving_queue_wait_seconds_bucket{le="+Inf"} 100',
        "serving_queue_wait_seconds_count 100",
        'serving_ttft_seconds_bucket{le="0.5"} 100',
        'serving_ttft_seconds_bucket{le="+Inf"} 100',
        'serving_inter_token_seconds_bucket{le="0.25"} 90',
        'serving_inter_token_seconds_bucket{le="1.0"} 99',
        'serving_inter_token_seconds_bucket{le="+Inf"} 100',
        "serving_kv_bytes_in_use 750",
        "serving_kv_bytes_total 1000",
        "serving_queued 4",
    ])
    sig = scrape_signals(text)
    # p99 rank 99 sits exactly at the 1.0 bucket's upper edge.
    assert 0.9 <= sig["queue_wait_p99_s"] <= 1.0
    assert sig["ttft_p99_s"] <= 0.5
    # p99 rank 99 sits exactly at the 1.0 bucket's upper edge.
    assert 0.9 <= sig["inter_token_p99_s"] <= 1.0
    assert sig["kv_utilization"] == 0.75
    assert sig["queued"] == 4.0


def test_scrape_signals_matches_inprocess_quantile():
    """Operator-side bucket interpolation agrees with the in-process
    Histogram.quantile the model server computes from the SAME data."""
    from kubeflow_tpu.observability.metrics import MetricRegistry

    reg = MetricRegistry()
    h = reg.histogram("serving_queue_wait_seconds", "t")
    for v in (0.001, 0.002, 0.01, 0.05, 0.05, 0.2, 0.7, 1.5, 3.0, 9.0):
        h.observe(v)
    sig = scrape_signals(reg.render())
    assert sig["queue_wait_p99_s"] == pytest.approx(h.quantile(0.99),
                                                   rel=1e-6)


def test_scrape_signals_empty_and_garbage_safe():
    assert scrape_signals("")["queue_wait_p99_s"] == 0.0
    sig = scrape_signals("not a metric line\nfoo{bar} nope\n")
    assert sig["kv_utilization"] == 0.0


def test_http_scrape_against_real_model_server():
    """Default fetch path end to end: scrape a live ModelServer's
    exposition after generation traffic and get finite signals."""
    from kubeflow_tpu.operators.inference import _http_fetch_signals
    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.server import ModelServer

    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=4, max_seq_len=32,
                     max_new_tokens=8, kv_layout="paged",
                     kv_block_size=8),
        port=0, batch_timeout_ms=2)
    server.start()
    try:
        server.handle_predict(
            "lm-test-tiny",
            {"instances": [{"tokens": [1, 2, 3],
                            "max_new_tokens": 4}]})
        sig = _http_fetch_signals(f"127.0.0.1:{server.port}")
        assert sig is not None
        assert sig["ttft_p99_s"] > 0
        assert 0 <= sig["kv_utilization"] <= 1
    finally:
        server.stop()
    assert _http_fetch_signals("127.0.0.1:1") is None  # dead replica


# ---------------------------------------------------------------------------
# Flash-crowd elasticity: predictive scale-up + newborn ramp guard
# ---------------------------------------------------------------------------


def test_predictive_scale_up_fires_before_any_observed_breach(env):
    """With autoscale.predictive the pool keeps a scrape history, fits
    the trend, and scales TO the projected need while every observed
    sample is still under target — the replicas are born before the
    SLO is breached, not after."""
    api, ctrl, clock, signals, _ = env
    api.create(_cr(autoscale={"cooldownSeconds": 30,
                              "scrapePeriodSeconds": 5,
                              "predictive": True,
                              "horizonSeconds": 30,
                              "maxStepUp": 4}))
    # Queue wait climbing 20ms/s but still under the 500ms target at
    # every observed point: 200ms -> 300ms -> 400ms over two periods.
    for wait_s in (0.2, 0.3, 0.4):
        signals["value"] = {**CALM, "queue_wait_p99_s": wait_s}
        ctrl.reconcile_all()
        clock["t"] += 5
    st = _status(api)
    # Projection at +30s is 1.0s = 2x target -> scale-to-N jumps the
    # pool straight from 2 to 4 (ceil(2 * 2.0)), not +1.
    assert st["replicas"] == 4
    assert "predictive scale-up" in st["lastScaleReason"]
    assert "queue_wait_p99" in st["lastScaleReason"]
    from kubeflow_tpu.operators.base import OPERATOR_METRICS
    assert "inference_predictive_scaleups_total" in \
        OPERATOR_METRICS.render()


def test_reactive_only_pool_never_scales_predictively(env):
    """The same climbing-but-under-target trace with predictive off
    (the default) holds steady: reactive behavior is unchanged."""
    api, ctrl, clock, signals, _ = env
    api.create(_cr())
    for wait_s in (0.2, 0.3, 0.4):
        signals["value"] = {**CALM, "queue_wait_p99_s": wait_s}
        ctrl.reconcile_all()
        clock["t"] += 5
    assert _status(api)["replicas"] == 2


def test_newborn_mid_cooldown_never_triggers_blind_scale_down(env):
    """Satellite regression: a replica born mid-cooldown that cannot
    be scraped yet must neither count as a calm vote nor let the
    seasoned replicas' calm shrink the pool out from under it — the
    scale-down that would kill the newborn the breach just paid for."""
    api, ctrl, clock, signals, _ = env
    young = {"unscrapeable": True}

    def fetch(addr):
        if "-r2." in addr and young["unscrapeable"]:
            return None  # newborn: weights pulling, no exposition yet
        return dict(signals["value"])

    ctrl.fetch_metrics = fetch
    api.create(_cr(autoscale={"cooldownSeconds": 30,
                              "scrapePeriodSeconds": 5},
                   warmup={"rampSeconds": 60}))
    ctrl.reconcile_all()
    signals["value"] = dict(BREACH)
    clock["t"] += 5
    ctrl.reconcile_all()  # birth of llm-r2 at t=5
    assert _status(api)["replicas"] == 3

    # Relief lands; the established replicas read LOW; the cooldown
    # (30s) elapses at t=40 — but the newborn is still ramping (<60s)
    # and unscrapeable. Without the ramp guard this reconcile would
    # scale down on two calm votes and kill the newborn blind.
    signals["value"] = dict(LOW)
    clock["t"] += 35
    ctrl.reconcile_all()
    st = _status(api)
    assert st["replicas"] == 3
    assert "still ramping" in st["lastScaleReason"]

    # Ramp over (t=70 > birth+60), the newborn scrapes calm like its
    # siblings: the normal cooled scale-down proceeds.
    young["unscrapeable"] = False
    clock["t"] += 30
    ctrl.reconcile_all()
    assert _status(api)["replicas"] == 2


def test_warmup_spec_renders_cache_volume_and_peer_chain(env):
    """spec.warmup flows into every replica: the shared compile-cache
    hostPath volume on all, --weight-peers only on replicas with a
    lower-indexed sibling to pull from (r0 must boot from the
    checkpoint — someone has to be first)."""
    api, ctrl, *_ = env
    api.create(_cr(warmup={
        "compileCacheDir": "/var/cache/kubeflow-tpu/compile",
        "peerWeights": True}))
    ctrl.reconcile_all()

    def replica(i):
        dep = api.get("apps/v1", "Deployment", f"llm-r{i}", NS)
        pod = dep["spec"]["template"]["spec"]
        return pod, pod["containers"][0]["args"]

    pod0, args0 = replica(0)
    pod1, args1 = replica(1)
    cache_flag = "--compile-cache-dir=/var/cache/kubeflow-tpu/compile"
    assert cache_flag in args0 and cache_flag in args1
    assert not any(a.startswith("--weight-peers") for a in args0)
    assert "--weight-peers=llm-r0.kubeflow:8500" in args1
    for pod in (pod0, pod1):
        vols = {v["name"]: v for v in pod.get("volumes", [])}
        assert vols["compile-cache"]["hostPath"]["path"] == \
            "/var/cache/kubeflow-tpu/compile"
        # XLA's executables go to the same volume as the manifest that
        # books them: the entry point takes the directory from JAX's own
        # variable and sets no other.
        assert {"name": "JAX_COMPILATION_CACHE_DIR",
                "value": "/var/cache/kubeflow-tpu/compile/xla"} in \
            pod["containers"][0]["env"]
