"""Observability subsystem tests: the unified metric registry (histogram
correctness under concurrency, exposition + label escaping), the
promtool-style exposition linter, request timelines (closing cleanly on
finish AND on decoder loop death — no leaked open spans), and the
HealthServer's corrected metric typing."""

import json
import math
import threading
import urllib.request

import jax
import pytest

from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.observability.lint import lint
from kubeflow_tpu.observability.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    Histogram,
    MetricRegistry,
    render_prometheus,
    type_line,
)
from kubeflow_tpu.observability.tracing import TraceStore, gen_request_id
from kubeflow_tpu.runtime import HealthServer
from kubeflow_tpu.serving.continuous import ContinuousDecoder


@pytest.fixture(scope="module")
def model():
    spec = get_model("lm-test-tiny")
    params = spec.init(jax.random.PRNGKey(0), spec.config)
    return spec, params


# ---------------------------------------------------------------------------
# Histogram correctness
# ---------------------------------------------------------------------------


def test_histogram_concurrent_observes_match_serial_reference():
    """N threads hammer one histogram; the final state must equal a
    serial pass over the same values: bucket counts, sum, count — and the
    cumulative exposition must be monotone."""
    import random

    h = Histogram()
    per_thread = 500
    threads_n = 8
    rngs = [random.Random(seed) for seed in range(threads_n)]
    values = [[rng.uniform(0, 2.0) for _ in range(per_thread)]
              for rng in rngs]

    def work(vals):
        for v in vals:
            h.observe(v)

    threads = [threading.Thread(target=work, args=(vals,))
               for vals in values]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    ref = Histogram()
    flat = [v for vals in values for v in vals]
    for v in flat:
        ref.observe(v)

    cum, total_sum, count = h.snapshot()
    ref_cum, ref_sum, ref_count = ref.snapshot()
    assert count == ref_count == threads_n * per_thread
    assert cum == ref_cum
    assert math.isclose(total_sum, ref_sum, rel_tol=1e-9)
    assert all(b >= a for a, b in zip(cum, cum[1:]))  # monotone
    assert cum[-1] == count  # +Inf bucket holds everything


def test_histogram_quantile_interpolation():
    h = Histogram(buckets=[1, 2, 4, 8])
    for v in [0.5, 1.5, 3.0, 3.5, 6.0]:
        h.observe(v)
    # p50 (rank 2.5 of 5) falls in the (2, 4] bucket holding ranks 3-4.
    q50 = h.quantile(0.5)
    assert 2.0 < q50 <= 4.0
    # Everything observed is <= 8; p100 never exceeds the top bound.
    assert h.quantile(1.0) <= 8.0
    h.observe(100.0)  # lands in +Inf; estimate saturates at top bound
    assert h.quantile(1.0) == 8.0
    assert Histogram().quantile(0.99) == 0.0  # empty → 0, not NaN


def test_registry_render_and_label_escaping_survive_lint():
    reg = MetricRegistry()
    reg.counter("demo_requests_total", "say \"hi\"", labels=("route",)) \
        .labels('we"ird\\ro\nute').inc(3)
    reg.gauge("demo_depth", "queue depth").set(7)
    reg.histogram("demo_latency_seconds", labels=("kind",)) \
        .labels("admit").observe(0.25)
    text = reg.render()
    assert type_line("demo_requests_total", "counter") in text
    assert 'route="we\\"ird\\\\ro\\nute"' in text
    assert lint(text) == []
    # Unlabeled gauge renders bare; histogram carries le after the label.
    assert "demo_depth 7\n" in text
    assert 'demo_latency_seconds_bucket{kind="admit",le="+Inf"} 1' in text


def test_registry_rejects_kind_and_label_conflicts():
    reg = MetricRegistry()
    reg.counter("x_total")
    assert reg.counter("x_total") is not None  # idempotent re-get
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("a",))
    with pytest.raises(ValueError):
        reg.counter("y_total").inc(-1)


def test_gauge_set_function_sampled_at_render():
    reg = MetricRegistry()
    depth = [3]
    reg.gauge("live_depth").set_function(lambda: depth[0])
    assert "live_depth 3\n" in reg.render()
    depth[0] = 9
    assert "live_depth 9\n" in reg.render()


# ---------------------------------------------------------------------------
# Exposition linter
# ---------------------------------------------------------------------------


def test_lint_accepts_render_prometheus_and_flags_violations():
    assert lint(render_prometheus({"a_total": 1, "b": 2.5})) == []

    # Sample with no TYPE declaration.
    assert lint("orphan_metric 1\n")
    # Counter family not named *_total.
    assert any("_total" in e
               for e in lint(type_line("bad", "counter") + "bad 1\n"))
    # Unknown kind, duplicate TYPE.
    assert lint(type_line("x", "chart") + "x 1\n")
    assert any("duplicate" in e for e in lint(
        type_line("x_total", "counter") * 2 + "x_total 1\n"))
    # Bad label escape.
    assert any("escape" in e for e in lint(
        type_line("e_total", "counter") + 'e_total{a="b\\q"} 1\n'))
    # Histogram: out-of-order buckets / missing +Inf / non-cumulative.
    base = type_line("h", "histogram")
    bad_order = base + ('h_bucket{le="1"} 2\nh_bucket{le="0.5"} 1\n'
                        'h_bucket{le="+Inf"} 3\nh_sum 1\nh_count 3\n')
    assert any("increasing" in e for e in lint(bad_order))
    no_inf = base + 'h_bucket{le="1"} 2\nh_sum 1\nh_count 2\n'
    assert any("+Inf" in e for e in lint(no_inf))
    not_cum = base + ('h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
                      'h_sum 1\nh_count 3\n')
    assert any("cumulative" in e for e in lint(not_cum))
    # The old HealthServer bug shape: a gauge-looking name typed counter
    # is caught by the *_total naming rule.
    assert lint(type_line("workqueue_depth", "counter")
                + "workqueue_depth 4\n")


def test_healthserver_types_gauges_as_gauges():
    """Satellite fix: /metrics used to stamp EVERY metric `counter`;
    queue depths and gauges were mislabeled. Through the shared renderer
    only *_total names are counters — and the output lints clean."""
    reg = MetricRegistry()
    reg.histogram("operator_demo_seconds", labels=("kind",)) \
        .labels("JaxJob").observe(0.01)
    h = HealthServer(0, lambda: {"queue_depth": 4, "adds_total": 9},
                     registry=reg)
    h.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{h.port}/metrics", timeout=10) as r:
            text = r.read().decode()
    finally:
        h.stop()
    assert type_line("queue_depth", "gauge") in text
    assert type_line("adds_total", "counter") in text
    assert type_line("operator_demo_seconds", "histogram") in text
    assert lint(text) == []


def test_operator_runtime_metrics_populated():
    """Reconciles land latency observations and workqueue counters in
    the shared operator registry, labeled by kind."""
    from kubeflow_tpu.operators.base import OPERATOR_METRICS, Controller

    class Probe(Controller):
        api_version = "kubeflow-tpu.org/v1"
        kind = "ObsProbe"

        def reconcile(self, obj):
            return None

    c = Probe(client=None)
    c._safe_reconcile({"metadata": {"name": "a"}})
    c._enqueue(("ns", "a"))
    c._enqueue(("ns", "a"), 0.5, retry=True)
    text = OPERATOR_METRICS.render()
    assert lint(text) == []
    assert 'operator_reconcile_seconds_count{kind="ObsProbe"} 1' in text
    assert 'operator_workqueue_adds_total{kind="ObsProbe"} 2' in text
    assert 'operator_workqueue_retries_total{kind="ObsProbe"} 1' in text
    assert 'operator_workqueue_depth{kind="ObsProbe"}' in text


# ---------------------------------------------------------------------------
# Timelines / trace store
# ---------------------------------------------------------------------------


def test_timeline_span_sum_equals_duration_and_ring_is_bounded():
    store = TraceStore(capacity=4)
    for i in range(6):
        tl = store.start(f"req-{i}")
        tl.event("submit")
        tl.event("admitted", slot=i)
        tl.event("first_token")
        tl.close("length")
    assert store.open_count == 0
    snap = store.snapshot()
    assert len(snap["finished"]) == 4  # ring evicted the oldest two
    rec = snap["finished"][-1]
    assert rec["request_id"] == "req-5"
    assert rec["status"] == "length"
    span_sum = sum(s["duration_ms"] for s in rec["spans"])
    assert span_sum == pytest.approx(rec["duration_ms"], abs=0.05)
    # Chrome export: one complete event per span, valid JSON.
    chrome = store.chrome_trace()
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 4 * 3  # submit→admitted→first_token→finish
    json.dumps(chrome)


def test_timeline_close_is_idempotent_and_caps_events():
    store = TraceStore(capacity=2, max_events=4)
    tl = store.start()
    assert len(tl.request_id) == 16
    for i in range(10):
        tl.event("dispatch", tokens=1)
    tl.close("eos")
    tl.close(error=RuntimeError("late"))  # no-op: first close wins
    rec = tl.to_dict()
    assert rec["status"] == "eos" and rec["error"] is None
    # 4 capped events + the terminal finish always lands.
    assert len(rec["events"]) == 5
    assert rec["events"][-1]["name"] == "finish"
    assert rec["dropped_events"] == 6


def test_decoder_timelines_close_on_finish_and_on_loop_death(model):
    """Every stream's timeline closes on normal completion; on decoder
    loop death (_fail_all — the PR-1 chaos failure mode) every live AND
    queued stream's timeline closes as an error. No leaked open spans."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8)
    try:
        rid = gen_request_id()
        h = d.submit([1, 2, 3], 4, request_id=rid)
        res = h.result(timeout=60)
        assert len(res["tokens"]) == 4
        recs = d.trace.find(rid)
        assert len(recs) == 1
        rec = recs[0]
        assert rec["status"] == "length"
        names = [e["name"] for e in rec["events"]]
        for expected in ("submit", "queued", "admitted", "prefill",
                         "first_token", "finish"):
            assert expected in names, (expected, names)
        assert names.index("first_token") < names.index("finish")
        span_sum = sum(s["duration_ms"] for s in rec["spans"])
        assert span_sum == pytest.approx(rec["duration_ms"], abs=0.05)

        # Loop death: fail everything; timelines must all close.
        h2 = d.submit([4, 5], 6, request_id="dying")
        d._fail_all(RuntimeError("chaos: loop died"))
        with pytest.raises(RuntimeError):
            h2.result(timeout=10)
        assert d.trace.open_count == 0
        dead = d.trace.find("dying")[0]
        assert dead["status"] == "error"
        assert "chaos" in dead["error"]
    finally:
        d.stop()
    assert d.trace.open_count == 0


def test_decoder_metrics_expose_histogram_quantiles(model):
    """Satellite: ttft_avg_s stays (dashboards read it) but
    histogram-backed p50/p90/p99 ride alongside, and the decoder's
    registry renders a lint-clean exposition."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8)
    try:
        for _ in range(3):
            d.generate([1, 2, 3], 4, timeout=60)
        m = d.metrics()
        assert m["ttft_avg_s"] > 0  # backward-compatible key
        for key in ("ttft_p50_s", "ttft_p90_s", "ttft_p99_s",
                    "inter_token_p50_s", "inter_token_p99_s",
                    "queue_wait_p50_s", "queue_wait_p99_s"):
            assert key in m
        assert 0 < m["ttft_p50_s"] <= m["ttft_p99_s"]
        assert d.trace.open_count == 0
        text = d.registry.render()
        assert lint(text) == []
        assert type_line("serving_ttft_seconds", "histogram") in text
        assert 'serving_dispatch_seconds_count{kind="admit"}' in text
        assert "serving_batch_occupancy_count" in text
    finally:
        d.stop()


def test_a_stalled_fetch_is_one_slow_round_logged_counted_and_served(
        monkeypatch, caplog):
    """A decoder whose ``fetch`` sleeps 0.3 s once: the round lands in the
    slow ring with ``fetch`` as the phase that held it, is logged once at
    WARNING after it ended, counted on ``/monitoring`` and served at
    ``/debug/rounds?slow=1``; ``stop()`` sums the rounds up in one line."""
    import time

    from kubeflow_tpu.observability import tracing
    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.server import ModelServer

    server = ModelServer(EngineConfig(model="lm-test-tiny", batch_size=2,
                                      max_seq_len=16, max_new_tokens=48,
                                      decode_mode="continuous"), port=0)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    d = server.decoder
    try:
        d.generate([1, 2, 3], 40, timeout=120)   # decode rounds get a median
        real, stalls = jax.device_get, []

        def stalling(tree):
            if threading.current_thread() is d._thread and not stalls:
                stalls.append(d._round)
                time.sleep(0.3)
            return real(tree)

        stream = d.submit([1, 2, 3], 30)
        next(stream.tokens(timeout=60))
        with caplog.at_level("WARNING", logger=tracing.log.name):
            monkeypatch.setattr(jax, "device_get", stalling)
            stream.result(timeout=60)
            monkeypatch.setattr(jax, "device_get", real)
            # (A loaded machine may hold up another round: the stalled one
            # is the one that took 0.3 s.)
            stalled = [r for r in d.rounds.slow() if r.wall_s >= 0.3]
            rec, = stalled
            assert rec.slow == "fetch" and rec.round == stalls[0]
            assert rec.phase_s["fetch"] >= 0.3 > rec.host_wall_s >= 0
            assert rec.median_s < 0.1 and rec.kind == "decode"
            lines = [r.getMessage() for r in caplog.records
                     if r.getMessage().startswith(f"slow round {rec.round} ")]
            assert len(lines) == 1 and "kind=decode" in lines[0]
            assert all(r.levelname == "WARNING" for r in caplog.records)
            m = d.metrics()
            assert m["slow_rounds"] == len(d.rounds.slow()) >= 1
            assert m["slow_round_seconds"] >= 0.25
            with urllib.request.urlopen(
                    base + "/monitoring/prometheus/metrics") as r:
                text = r.read().decode()
            assert lint(text) == []
            in_fetch = sum(1 for r in d.rounds.slow() if r.slow == "fetch")
            assert f'{tracing.SLOW_COUNTER}{{phase="fetch"}} {in_fetch}' \
                in text
            with urllib.request.urlopen(base + "/debug/rounds?slow=1") as r:
                served = json.loads(r.read())
            assert "rounds" not in served
            assert served["slow_total"] == m["slow_rounds"]
            one, = [x for x in served["slow"] if x["round"] == rec.round]
            assert one["slow"] == "fetch" and one["phase_ms"]["fetch"] >= 300
            with urllib.request.urlopen(base + "/debug/rounds") as r:
                everything = json.loads(r.read())
            assert len(everything["rounds"]) == d.rounds.rounds
            with urllib.request.urlopen(
                    base + "/debug/rounds?slow=1&format=chrome") as r:
                chrome = json.loads(r.read())
            events = [e for e in chrome["traceEvents"] if e["ph"] == "X"
                      and e["args"]["round"] == rec.round]
            assert events[0]["name"] == tracing.SPAN_ROUND
            assert {e["name"] for e in events[1:]} >= {"sched.fetch"}
            assert sum(e["dur"] for e in events[1:]) <= events[0]["dur"] + 1
            caplog.clear()
            server.stop()
            summary, = [r.getMessage() for r in caplog.records]
            assert summary.startswith("scheduler rounds ")
            assert f"round {rec.round} kind=decode" in summary
    finally:
        server.stop()


def test_a_busy_thread_beside_the_scheduler_shows_as_time_off_the_cpu(model):
    """``host_wall_s - host_cpu_s`` is the time the scheduler thread had
    work (the round less its ``fetch`` and ``idle``) and was not running.
    With another Python thread spinning on the GIL beside it some of every
    few rounds is that; no amount is asserted, only the sum's sign and that
    a round's CPU seconds lie between 0 and the round's own."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=64)
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(2000))

    spinner = threading.Thread(target=spin, daemon=True)
    try:
        d.generate([1, 2, 3], 8, timeout=60)
        spinner.start()
        d.generate([1, 2, 3], 64, timeout=120)
        stop.set()
        recs = d.rounds.recent()
        assert all(0 <= r.host_cpu_s <= r.wall_s + 1e-5 for r in recs)
        assert sum(r.host_wall_s - r.host_cpu_s for r in recs) > 0
    finally:
        stop.set()
        d.stop()


def test_default_latency_buckets_are_log_spaced():
    b = DEFAULT_LATENCY_BUCKETS
    assert b[0] == pytest.approx(1e-4) and b[-1] == pytest.approx(1e2)
    ratios = [y / x for x, y in zip(b, b[1:])]
    assert all(r == pytest.approx(ratios[0], rel=1e-6) for r in ratios)


def test_timeline_to_dict_consistent_with_concurrent_close():
    """PR-11 regression (tpu-lint lock-inconsistent-guard): to_dict()
    read status/error/dropped without the timeline lock while close()
    wrote them — /debug/requests could render status "error" with the
    error text missing. The snapshot is now taken under the lock: the
    pair is always consistent, whichever side of close() it lands."""
    for i in range(50):
        store = TraceStore()
        tl = store.start(f"rid{i:03d}")
        tl.event("submit")
        out: list[dict] = []
        t = threading.Thread(target=lambda: out.append(tl.to_dict()))
        t.start()
        tl.close(error=RuntimeError("boom"))
        t.join(timeout=10)
        d = out[0]
        if d["status"] == "error":
            assert d["error"] == "boom"
        else:
            assert d["status"] == "open" and d["error"] is None
    assert tl.open is False


def test_token_exchange_runs_outside_client_lock():
    """PR-11 regression (tpu-lint lock-blocking-call, the PR-9 stall
    class): TokenClient.token() held the client lock across the HTTP
    exchange, serializing every concurrent caller behind one slow
    gatekeeper for up to the full timeout. The exchange now runs
    unlocked — the lock must be acquirable while a refresh is in
    flight."""
    import time as _time
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from kubeflow_tpu.observability.collector import TokenClient

    class SlowIssuer(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            body = json.dumps({"id_token": "tok",
                               "expires_in": 3600}).encode()
            _time.sleep(0.6)
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), SlowIssuer)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        tc = TokenClient(
            f"http://127.0.0.1:{httpd.server_address[1]}/token",
            "prober", "sa-key")
        refresher = threading.Thread(target=tc.token, daemon=True)
        refresher.start()
        _time.sleep(0.2)  # exchange now in flight on the refresher
        got = tc._lock.acquire(timeout=0.2)
        assert got, "client lock held across the network exchange"
        tc._lock.release()
        refresher.join(timeout=10)
        assert tc.token() == "tok"  # cached — no second slow exchange
    finally:
        httpd.shutdown()
