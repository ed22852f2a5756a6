"""Continuous-batching decode tests: lockstep parity, early return for
short requests, token streaming over chunked REST and gRPC streams, EOS.

The reference's serving tests stop at TF-Serving RPC smoke checks
(testing/test_tf_serving.py); these additionally pin the scheduler's
correctness against the one-shot compiled path.
"""

import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.decode import generate
from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.serving import continuous
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.engine import EngineConfig
from kubeflow_tpu.serving.server import ModelServer


@pytest.fixture(scope="module")
def model():
    spec = get_model("lm-test-tiny")
    params = spec.init(jax.random.PRNGKey(0), spec.config)
    return spec, params


@pytest.fixture()
def decoder(model):
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=4, prefill_len=16,
                          max_new_tokens=8)
    yield d
    d.stop()


def test_greedy_parity_with_lockstep_generate(model, decoder):
    """Greedy decoding through the continuous scheduler must produce the
    same tokens as the one-shot compiled ``generate`` call."""
    spec, params = model
    prompts = [[1, 2, 3], [7, 5], [9, 9, 9, 9, 2]]
    want = 6

    b = len(prompts)
    t0 = max(len(p) for p in prompts)
    toks = np.zeros((b, t0), np.int32)
    lengths = np.zeros((b,), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
        lengths[i] = len(p)
    ref, _last = generate(
        params, jnp.asarray(toks), jnp.asarray(lengths), spec.config,
        max_new_tokens=want, key=jax.random.PRNGKey(0),
        temperature=jnp.zeros((b,)),
    )
    ref = np.asarray(ref)

    handles = [decoder.submit(p, want) for p in prompts]
    for i, h in enumerate(handles):
        res = h.result(timeout=60)
        assert res["tokens"] == ref[i].tolist(), f"prompt {i} diverged"
        assert res["finish_reason"] == "length"


def test_short_request_returns_before_long_peer(model):
    """The decoupling the lockstep batch lacks: a 1-token request submitted
    WITH a long one finishes as soon as its own token lands. (The peer is
    64 tokens long so that a loaded machine's late submit still finds it
    mid-flight: at 8 its seven rounds could pass first.)"""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=4, prefill_len=16,
                          max_new_tokens=64)
    try:
        long_h = d.submit([1, 2, 3], 64)
        next(long_h.tokens(timeout=60))  # long is mid-flight
        short_h = d.submit([4, 5], 1)
        short_res = short_h.result(timeout=60)
        long_running_at_short_done = not long_h._req.done.is_set()
        long_res = long_h.result(timeout=60)
    finally:
        d.stop()
    assert len(short_res["tokens"]) == 1
    assert len(long_res["tokens"]) == 64
    assert long_running_at_short_done


def test_tokens_stream_incrementally(decoder):
    h = decoder.submit([3, 1], 5)
    seen = list(h.tokens(timeout=60))
    assert len(seen) == 5
    assert h.result(timeout=5)["tokens"] == seen


def test_slot_reuse_beyond_capacity(model):
    """More requests than slots: the queue drains as rows free up, and a
    reused slot must not leak the previous occupant's cache."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8)
    try:
        solo = d.submit([2, 4, 6], 4).result(timeout=60)
        handles = [d.submit([2, 4, 6], 4) for _ in range(5)]
        for h in handles:
            assert h.result(timeout=60)["tokens"] == solo["tokens"]
    finally:
        d.stop()


def test_eos_frees_slot_early(model):
    spec, params = model
    probe = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                              max_new_tokens=8)
    try:
        toks = probe.generate([1, 2, 3], 6)["tokens"]
    finally:
        probe.stop()
    eos = toks[2]  # the third greedy token becomes the stop id
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8, eos_id=eos)
    try:
        res = d.generate([1, 2, 3], 6)
        assert res["tokens"] == toks[:3]
        assert res["finish_reason"] == "eos"
    finally:
        d.stop()


def test_want_zero_returns_prefill_logits(decoder):
    res = decoder.generate([5, 6, 7], 0)
    assert res["tokens"] == []
    assert res["prefill_logits"].shape == (256,)


# ---------------------------------------------------------------------------
# Server surfaces
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    s = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=4, max_seq_len=16,
                     max_new_tokens=8),
        port=0, grpc_port=0, batch_timeout_ms=2,
    )
    s.start()
    yield s
    s.stop()


def _post_json(port, path, payload):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("POST", path, body=json.dumps(payload).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, json.loads(body)


def test_rest_stream_chunked(server):
    """`"stream": true` returns chunked JSON lines, one per token, with the
    first record arriving before the generation completes."""
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
    conn.request(
        "POST", "/v1/models/lm-test-tiny:predict",
        body=json.dumps({"stream": True, "instances": [
            {"tokens": [1, 2, 3], "max_new_tokens": 6},
        ]}).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "application/jsonlines"
    records = []
    buf = b""
    while True:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if line.strip():
                records.append(json.loads(line))
    conn.close()
    tokens = [r["token"] for r in records if "token" in r]
    final = records[-1]
    assert final["done"] and final["tokens"] == tokens
    assert len(tokens) == 6
    assert final["ttft_ms"] >= 0

    # Non-streamed request over the same server agrees (greedy).
    status, out = _post_json(
        server.port, "/v1/models/lm-test-tiny:predict",
        {"instances": [{"tokens": [1, 2, 3], "max_new_tokens": 6}]},
    )
    assert status == 200
    assert out["predictions"][0]["tokens"] == tokens


def test_rest_stream_validation_fails_before_headers(server):
    status, body = _post_json(
        server.port, "/v1/models/lm-test-tiny:predict",
        {"stream": True, "instances": [{"tokens": [1]},
                                       {"tokens": [2]}]},
    )
    assert status == 400
    assert "exactly one instance" in body["error"]


def test_grpc_stream(server):
    import grpc

    from kubeflow_tpu.serving.grpc_server import stream_stub

    with grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}") as chan:
        do_stream = stream_stub(chan)
        records = list(do_stream(
            "lm-test-tiny", {"tokens": [4, 4], "max_new_tokens": 4}
        ))
    tokens = [r["token"] for r in records if "token" in r]
    assert len(tokens) == 4
    assert records[-1]["done"] and records[-1]["tokens"] == tokens


def test_mixed_generation_and_predict_instances(server):
    """One request mixing a generation and a plain predict: the generation
    rides the continuous decoder, the predict rides the batcher, and both
    come back in order."""
    status, out = _post_json(
        server.port, "/v1/models/lm-test-tiny:predict",
        {"instances": [
            {"tokens": [1, 2, 3], "max_new_tokens": 3},
            {"tokens": [1, 2, 3]},
        ]},
    )
    assert status == 200
    gen, plain = out["predictions"]
    assert len(gen["tokens"]) == 3
    assert len(plain["logits"]) == 256
    # Greedy first generated token == the plain predict's argmax.
    assert gen["next_token"] == plain["next_token"]


def test_decoder_metrics_exposed(server):
    # The generation tests above drove the decoder; counters must show it.
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    conn.request("GET", "/monitoring/prometheus/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    assert "serving_tokens_emitted_total" in text
    assert "serving_ttft_avg_seconds" in text


def test_sustained_mixed_lengths_all_complete(model):
    """A burst of ragged-length requests through a small-slot decoder all
    complete with their own lengths (continuous admission under churn)."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=3, prefill_len=16,
                          max_new_tokens=8)
    try:
        t0 = time.perf_counter()
        wants = [1, 8, 2, 5, 3, 8, 1, 4]
        handles = [d.submit([i + 1], w) for i, w in enumerate(wants)]
        for h, w in zip(handles, wants):
            assert len(h.result(timeout=120)["tokens"]) == w
        assert time.perf_counter() - t0 < 120
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# Chunked decode (K steps fused per device dispatch — high-RTT-link mode)
# ---------------------------------------------------------------------------


def test_chunked_greedy_parity(model):
    """chunk_size>1 fuses K steps into one dispatch but must emit exactly
    the tokens the per-step path emits."""
    spec, params = model
    prompts = [[1, 2, 3], [7, 5], [9, 9, 9, 9, 2]]
    per_step = ContinuousDecoder(params, spec.config, slots=4,
                                 prefill_len=16, max_new_tokens=8)
    try:
        ref = [per_step.generate(p, 6)["tokens"] for p in prompts]
    finally:
        per_step.stop()
    chunked = ContinuousDecoder(params, spec.config, slots=4,
                                prefill_len=16, max_new_tokens=8,
                                chunk_size=4)
    try:
        handles = [chunked.submit(p, 6) for p in prompts]
        for h, r in zip(handles, ref):
            assert h.result(timeout=60)["tokens"] == r
        # The fused path must actually batch: 18 tokens emitted in far
        # fewer device round-trips than the per-token path's one-per-step
        # (admission rounds ramp with a single un-fused step for TTFT).
        assert chunked.dispatches < chunked.steps
    finally:
        chunked.stop()


def test_chunked_eos_parks_on_device(model):
    """EOS inside a fused chunk stops the row on device: the request
    finishes with reason 'eos' and no post-EOS tokens leak."""
    spec, params = model
    probe = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                              max_new_tokens=8)
    try:
        toks = probe.generate([1, 2, 3], 6)["tokens"]
    finally:
        probe.stop()
    eos = toks[2]  # third greedy token becomes the stop id (mid-chunk)
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8, eos_id=eos, chunk_size=4)
    try:
        res = d.generate([1, 2, 3], 6)
        assert res["tokens"] == toks[:3]
        assert res["finish_reason"] == "eos"
        # Slot freed by the parking: a follow-up request reuses it cleanly.
        assert d.generate([1, 2, 3], 2)["tokens"] == toks[:2]
    finally:
        d.stop()


def test_batched_admission_parity_and_dispatch_count(model):
    """A burst admitted together (one prefill + one insert dispatch)
    produces exactly the tokens sequential admission produces, and the
    admission cost is 2 dispatches per ROUND, not per request."""
    spec, params = model
    prompts = [[1, 2, 3], [7, 5], [9, 9, 9, 9, 2], [4]]
    ref_d = ContinuousDecoder(params, spec.config, slots=1, prefill_len=16,
                              max_new_tokens=8)
    try:
        # slots=1 forces one-at-a-time admission — the sequential oracle.
        ref = [ref_d.generate(p, 6)["tokens"] for p in prompts]
    finally:
        ref_d.stop()

    d = ContinuousDecoder(params, spec.config, slots=4, prefill_len=16,
                          max_new_tokens=8)
    try:
        handles = [d.submit(p, 6) for p in prompts]
        for h, r in zip(handles, ref):
            assert h.result(timeout=60)["tokens"] == r
        m = d.metrics()
        assert m["requests_admitted"] == 4
        # Fused admission: ONE dispatch per admission round (usually one
        # round for the whole burst) — far below the 8 of per-request
        # prefill+insert pairs.
        assert m["prefill_dispatches"] <= 3
    finally:
        d.stop()


def test_batched_admission_mixed_wants_and_pure_prefill(model):
    """A batch mixing normal requests with want=0 pure prefills: the
    prefills return logits immediately, the rest decode to completion."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=4, prefill_len=16,
                          max_new_tokens=8)
    try:
        probe = d.submit([1, 2, 3], 2)
        score = d.submit([5, 6], 0)        # pure prefill
        long = d.submit([7], 8)
        r_score = score.result(timeout=60)
        assert r_score["tokens"] == []
        assert r_score["prefill_logits"] is not None
        assert len(probe.result(timeout=60)["tokens"]) == 2
        assert len(long.result(timeout=60)["tokens"]) == 8
        # Same logits as a solo prefill of the same prompt.
        solo = d.submit([5, 6], 0).result(timeout=60)
        np.testing.assert_allclose(r_score["prefill_logits"],
                                   solo["prefill_logits"], rtol=2e-5,
                                   atol=2e-5)
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# Decode-loop crash propagation (no stream may hang out its timeout)
# ---------------------------------------------------------------------------


def test_loop_crash_fails_inflight_and_queued_promptly(model, monkeypatch):
    """If the decode loop dies, every live StreamHandle — mid-decode AND
    still queued — must get the error immediately, not a 60s timeout."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=1, prefill_len=16,
                          max_new_tokens=64)
    try:
        inflight = d.submit([1, 2, 3], 64)
        next(inflight.tokens(timeout=60))  # decoding is underway
        boom = RuntimeError("injected decode failure")
        real = continuous.decode_step
        armed = threading.Event()

        def explode(*a, **k):
            # Armed only once the second request is queued: a loop that
            # died first would refuse the submit itself.
            if armed.is_set():
                raise boom
            return real(*a, **k)

        monkeypatch.setattr("kubeflow_tpu.serving.continuous.decode_step",
                            explode)
        queued = d.submit([4, 5], 4)  # slots=1: this one sits in _pending
        armed.set()
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="injected decode failure"):
            inflight.result(timeout=10)
        with pytest.raises(RuntimeError, match="injected decode failure"):
            queued.result(timeout=10)
        assert time.perf_counter() - t0 < 5  # propagated, not timed out
        with pytest.raises(RuntimeError, match="stopped"):
            d.submit([1], 1)  # the dead decoder refuses new work clearly
    finally:
        d.stop()


def test_loop_crash_during_admission_fails_popped_requests(model,
                                                           monkeypatch):
    """A request popped from the queue but not yet registered in a slot
    when admission blows up must still be failed (it is visible to
    neither the slot sweep nor the pending deque)."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8)
    try:
        monkeypatch.setattr(
            "kubeflow_tpu.serving.continuous.admit_rows_and_step",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("injected admission failure")))
        h = d.submit([1, 2, 3], 4)
        with pytest.raises(RuntimeError, match="injected admission"):
            h.result(timeout=10)
    finally:
        d.stop()


def test_stream_iteration_raises_loop_error(model, monkeypatch):
    """tokens() consumers (the streaming REST/gRPC paths) see the crash
    as a raised error on the iterator, not a silent stall."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=64)
    try:
        h = d.submit([1, 2, 3], 64)
        it = h.tokens(timeout=60)
        next(it)
        monkeypatch.setattr(
            "kubeflow_tpu.serving.continuous.decode_step",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("injected decode failure")))
        with pytest.raises(RuntimeError, match="injected decode failure"):
            for _ in it:
                pass
    finally:
        d.stop()


def test_chunked_mixed_lengths_all_complete(model):
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=3, prefill_len=16,
                          max_new_tokens=8, chunk_size=4)
    try:
        wants = [1, 8, 2, 5, 3, 8]
        handles = [d.submit([i + 1], w) for i, w in enumerate(wants)]
        for h, w in zip(handles, wants):
            assert len(h.result(timeout=120)["tokens"]) == w
    finally:
        d.stop()


def test_metrics_snapshot_consistent_under_load(model):
    """PR-11 regression (tpu-lint lock-inconsistent-guard): several
    counters (steps, prefix_misses, prefix_inserts, queue depth) were
    mutated outside the metrics lock while metrics() snapshotted under
    it — torn reads, the PR-4 bug class. Hammer metrics() from a side
    thread during live traffic and assert the snapshots stay sane."""
    import threading

    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=4, prefill_len=16,
                          max_new_tokens=8, prefix_cache_slots=4,
                          prefix_cache_min_len=4, kv_layout="paged",
                          kv_block_size=4)
    errors: list[Exception] = []
    stop = threading.Event()

    def hammer():
        last_steps = 0
        try:
            while not stop.is_set():
                m = d.metrics()
                # Monotone under the lock-guarded snapshot; a torn
                # read could observe a lost update going backwards.
                assert m["decode_steps"] >= last_steps
                last_steps = m["decode_steps"]
                assert m["queued"] >= 0
                assert m["prefill_tokens"] >= 0
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        handles = [d.submit([1 + i, 2, 3, 4, 5], 6) for i in range(12)]
        for h in handles:
            h.result(timeout=60)
    finally:
        stop.set()
        t.join(timeout=10)
        d.stop()
    assert not errors, errors


def test_stop_with_queued_requests_fails_them_cleanly(model):
    """PR-11 regression: stop() iterated the live pending deque after a
    bounded join — racing the scheduler's popleft. It now snapshots the
    queue under the cv; every queued request still gets its terminal
    error."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8)
    handles = [d.submit([1, 2, 3], 8) for _ in range(6)]
    d.stop()
    for h in handles:
        with pytest.raises((RuntimeError, TimeoutError)):
            h.result(timeout=5)


def test_plain_decode_steps_run_one_ahead_of_their_tokens(model):
    """While a row decodes, step n+1 is enqueued before step n's tokens
    are fetched; the last step of a busy period is taken at once."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8)
    ahead = []
    deliver = d._deliver

    def spy(*step):
        ahead.append((d.dispatches, d._inflight is not None))
        deliver(*step)

    d._deliver = spy
    try:
        res = d.generate([1, 2, 3], 8, timeout=60)
        assert len(res["tokens"]) == 8
        deadline = time.time() + 10
        while d._inflight is not None and time.time() < deadline:
            time.sleep(0.01)
        assert d._inflight is None
    finally:
        d.stop()
    # The admission's fused step gave token 1; plain dispatch k (1-based)
    # carries token k+1 and is fetched after dispatch k+1 went out.
    assert ahead[:7] == [(k + 1, True) for k in range(1, 8)]
    # Dispatch 8 ran ahead of the finish; it emits nothing and nothing is
    # left in flight behind it.
    assert ahead[7:] == [(8, False)]
    assert d.metrics()["tokens_emitted"] == 8


def test_admission_takes_the_step_in_flight_first(model):
    """A request admitted while another decodes one step ahead: both get
    the tokens they get alone, each in order."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=4, prefill_len=16,
                          max_new_tokens=24)
    try:
        alone = [d.generate(p, 24, timeout=60)["tokens"]
                 for p in ([1, 2, 3], [7, 5])]
        first = d.submit([1, 2, 3], 24)
        stream = first.tokens(timeout=60)
        got = [next(stream) for _ in range(5)]
        second = d.submit([7, 5], 24)
        assert second.result(timeout=60)["tokens"] == alone[1]
        assert got + list(stream) == alone[0]
    finally:
        d.stop()
