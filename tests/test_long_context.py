"""Long-context serving tests: chunked prefill interleaved with decode,
context-parallel chunk attention, and the pipeline-parallel decoder.

The invariant under test everywhere: a chunked admission is the SAME
admission, just dispatched in bounded pieces — greedy, sampled,
speculative, prefix-hit, int8, and tp-sharded token streams must be
byte-identical to a monolithic decoder whose prefill window covers the
whole prompt (interior chunks consume no RNG; the final chunk is
exactly the pinned prefix-hit admission), prompts past
``max_prompt_len`` must be a clean ``PromptTooLong`` (HTTP 413), a
mid-chain slot must never be a QoS suspension victim, and a live
weight push mid-chain must restart the whole admission under the new
epoch. Runs on the conftest 8-device CPU mesh; cp legs use tp=1 (the
combined tp x cp partition hits the CPU backend's PartitionId gap, the
same class conftest documents for the training pipeline tests).
"""

from __future__ import annotations

import json
import socket
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from kubeflow_tpu.models.registry import get_model  # noqa: E402
from kubeflow_tpu.parallel.mesh import serving_mesh  # noqa: E402
from kubeflow_tpu.parallel.pipeline import (  # noqa: E402
    stage_layer_ranges,
)
from kubeflow_tpu.serving import continuous as cont  # noqa: E402
from kubeflow_tpu.serving.continuous import (  # noqa: E402
    ContinuousDecoder,
    PromptTooLong,
)
from kubeflow_tpu.serving.qos import QosPolicy, TenantSpec  # noqa: E402

# 80 tokens: 2.5x the 32-token dense window, mid-block tail at block=8.
LONG = [(j * 7 + 3) % 97 + 1 for j in range(80)]
SHORT = [5, 11, 7, 3, 13, 2, 17, 9, 4, 6, 19, 8]


@pytest.fixture(scope="module")
def tiny():
    # 4 kv heads so tp=2 shards evenly; f32 so greedy is bitwise
    # across chunkings and mesh shapes.
    spec = get_model("lm-test-tiny", n_kv_heads=4, dtype=jnp.float32)
    return spec, spec.init(jax.random.PRNGKey(0), spec.config)


@pytest.fixture(scope="module")
def tiny_v2(tiny):
    spec, _ = tiny
    return spec.init(jax.random.PRNGKey(1), spec.config)


def _decoder(tiny, **kw):
    spec, params = tiny
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_len", 32)
    kw.setdefault("max_new_tokens", 16)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("stream_timeout_s", 120.0)
    return ContinuousDecoder(params, spec.config, **kw)


def _chunked(tiny, chunk=8, **kw):
    kw.setdefault("max_prompt_len", 112)
    return _decoder(tiny, prefill_chunk_tokens=chunk, **kw)


def _wide(tiny, **kw):
    # Monolithic reference: one prefill window covering max_prompt_len.
    return _decoder(tiny, prefill_len=112, **kw)


PROBES = [LONG, LONG[:40], SHORT, [1, 2, 3]]


def _probe(d, want=6, temperature=0.0):
    return [d.generate(p, want, temperature=temperature,
                       timeout=120)["tokens"] for p in PROBES]


# ---------------------------------------------------------------------------
# Mesh and stage plumbing
# ---------------------------------------------------------------------------


def test_serving_mesh_shapes():
    shape = dict(serving_mesh(2, cp=2, pp=2).shape)
    assert shape["tensor"] == 2
    assert shape["sequence"] == 2
    assert shape["pipeline"] == 2
    assert shape["data"] == 1
    shape = dict(serving_mesh(2).shape)
    assert shape["tensor"] == 2
    assert shape["sequence"] == 1 and shape["pipeline"] == 1
    with pytest.raises(ValueError):
        serving_mesh(4, cp=4)  # 16 chips > the 8-device CPU host
    with pytest.raises(ValueError):
        serving_mesh(0)
    with pytest.raises(ValueError):
        serving_mesh(1, pp=0)


def test_stage_layer_ranges():
    assert stage_layer_ranges(8, 2) == [(0, 4), (4, 8)]
    assert stage_layer_ranges(2, 1) == [(0, 2)]
    with pytest.raises(ValueError):
        stage_layer_ranges(3, 2)  # layers must split evenly
    with pytest.raises(ValueError):
        stage_layer_ranges(4, 0)


# ---------------------------------------------------------------------------
# Byte-identity matrix: chunked == monolithic, every serving mode
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wide_greedy(tiny):
    d = _wide(tiny)
    try:
        return _probe(d)
    finally:
        d.stop()


def test_greedy_byte_identity_chunked(tiny, wide_greedy):
    d = _chunked(tiny)
    try:
        got = _probe(d)
        m = d.metrics()
    finally:
        d.stop()
    assert got == wide_greedy
    assert m["prefill_chunks"] > 0  # the chain actually ran


@pytest.mark.parametrize("plen", [63, 64, 65])
def test_chunk_boundary_lengths(tiny, plen):
    """Prompt lengths straddling an exact chunk multiple: the final
    chunk may be full-width, one token, or chunk-1 — all must equal
    the monolithic admission."""
    prompt = LONG[:plen] if plen <= len(LONG) else LONG + LONG[:plen - 80]
    w = _wide(tiny)
    try:
        want = w.generate(prompt, 6, timeout=120)["tokens"]
    finally:
        w.stop()
    d = _chunked(tiny, chunk=8)
    try:
        got = d.generate(prompt, 6, timeout=120)["tokens"]
    finally:
        d.stop()
    assert got == want


def test_sampled_byte_identity_chunked(tiny):
    w = _wide(tiny, seed=7)
    try:
        want = _probe(w, temperature=0.8)
    finally:
        w.stop()
    d = _chunked(tiny, seed=7)
    try:
        got = _probe(d, temperature=0.8)
    finally:
        d.stop()
    assert got == want


def test_speculative_byte_identity_chunked(tiny, wide_greedy):
    d = _chunked(tiny, speculative_k=3)
    try:
        got = _probe(d)
        m = d.metrics()
    finally:
        d.stop()
    assert got == wide_greedy
    assert m["spec_verify_dispatches"] > 0  # speculation actually ran
    assert m["prefill_chunks"] > 0


def test_prefix_hit_byte_identity_chunked(tiny):
    """A chunked re-admission over a cached prefix: the chain starts at
    the pinned prefix length, and tokens still equal the monolithic
    decoder with the same cache."""
    kw = dict(prefix_cache_slots=4, prefix_cache_min_len=8)
    probes = [LONG, LONG + [23, 29], LONG + [31, 37]]
    w = _wide(tiny, **kw)
    try:
        want = [w.generate(p, 6, timeout=120)["tokens"] for p in probes]
    finally:
        w.stop()
    d = _chunked(tiny, **kw)
    try:
        got = [d.generate(p, 6, timeout=120)["tokens"] for p in probes]
        m = d.metrics()
    finally:
        d.stop()
    assert got == want
    assert m["prefix_hits"] >= 2  # followers rode the trie
    assert m["prefill_chunks"] > 0


def test_int8_byte_identity_chunked(tiny):
    w = _wide(tiny, kv_dtype="int8")
    try:
        want = _probe(w)
    finally:
        w.stop()
    d = _chunked(tiny, kv_dtype="int8")
    try:
        got = _probe(d)
        m = d.metrics()
    finally:
        d.stop()
    assert got == want
    assert m["prefill_chunks"] > 0


def test_tp2_byte_identity_chunked(tiny, wide_greedy):
    """Chunked admission over a tp=2 tensor mesh (no cp: the combined
    tp x cp SPMD program is the CPU backend's PartitionId gap)."""
    d = _chunked(tiny, tp_shards=2)
    try:
        got = _probe(d)
        m = d.metrics()
    finally:
        d.stop()
    assert got == wide_greedy
    assert m["prefill_chunks"] > 0


def test_decode_progresses_while_a_long_admission_chunks(tiny):
    """A live decode stream keeps stepping between the chunks of an
    80-token admission (nine interior chunks of 8 and the final admit):
    the chain never holds the scheduler for its whole length, and the
    stream's tokens equal an undisturbed run's."""
    d = _chunked(tiny, max_new_tokens=64)
    try:
        stream = d.submit(SHORT, 64).tokens(timeout=120)
        got = [next(stream)]  # live before the long prompt arrives
        before = d.metrics()
        first = next(iter(d.submit(LONG, 4).tokens(timeout=120)))
        during = d.metrics()
        got.extend(stream)
        chunks = during["prefill_chunks"] - before["prefill_chunks"]
        assert chunks == (len(LONG) - 1) // 8
        assert (during["decode_dispatches"]
                - before["decode_dispatches"]) >= chunks
        assert got == d.generate(SHORT, 64, timeout=120)["tokens"]
        assert first == d.generate(LONG, 1, timeout=120)["tokens"][0]
    finally:
        d.stop()


def test_no_leaked_blocks_after_chunked_drain(tiny):
    d = _chunked(tiny, prefix_cache_slots=4, prefix_cache_min_len=8)
    try:
        _probe(d)
        with d._prefix_lock:
            while d.prefix_cache.evict_lru():
                pass
        assert d.metrics()["kv_blocks_in_use"] == 0
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# Context-parallel and pipeline-parallel parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cp", [2, 4])
def test_cp_ring_prefill_parity(tiny, cp):
    """Ring chunk attention over cp sequence shards: byte-identical to
    the cp=1 chunked decoder (weights replicated over the axis; only
    chunk and final-admit dispatches see the ring)."""
    base = _chunked(tiny, chunk=16)
    try:
        want = [base.generate(p, 4, timeout=120)["tokens"]
                for p in (LONG, SHORT)]
    finally:
        base.stop()
    d = _chunked(tiny, chunk=16, cp_shards=cp)
    try:
        got = [d.generate(p, 4, timeout=120)["tokens"]
               for p in (LONG, SHORT)]
        m = d.metrics()
    finally:
        d.stop()
    assert got == want
    assert m["cp_shards"] == cp


def test_pp2_decoder_parity(tiny):
    """Layer-sharded decoder: stacked params + the pool's L dim over
    two pipeline stages, host code unchanged — tokens byte-identical
    to the unsharded decoder — including through a chunked chain."""
    base = _chunked(tiny)
    try:
        want = _probe(base)
    finally:
        base.stop()
    d = _chunked(tiny, pp_stages=2)
    try:
        got = _probe(d)
        m = d.metrics()
    finally:
        d.stop()
    assert got == want
    assert m["pp_stages"] == 2


def test_pp_validation_errors(tiny):
    with pytest.raises(ValueError):
        _decoder(tiny, pp_stages=3)  # 2 layers don't split into 3
    with pytest.raises(ValueError):
        _decoder(tiny, pp_stages=2, kv_fused=True)


def test_cp_validation_errors(tiny):
    with pytest.raises(ValueError):
        _decoder(tiny, cp_shards=2)  # cp requires chunked prefill
    with pytest.raises(ValueError):
        _chunked(tiny, cp_shards=3)  # power of two only


# ---------------------------------------------------------------------------
# PromptTooLong: the 413 boundary, decoder and HTTP server
# ---------------------------------------------------------------------------


def test_prompt_too_long_boundary(tiny):
    d = _chunked(tiny, chunk=16, max_prompt_len=112)
    try:
        edge = [(i % 90) + 1 for i in range(112)]
        assert len(d.generate(edge, 4, timeout=120)["tokens"]) == 4
        with pytest.raises(PromptTooLong):
            d.generate(edge + [1], 4, timeout=120)
        m = d.metrics()
    finally:
        d.stop()
    assert m["prompt_rejected_too_long"] == 1
    assert m["max_prompt_len"] == 112


def test_unchunked_prompt_beyond_window_still_rejects(tiny):
    """Without chunking the ceiling is the dense window — and crossing
    it must now RAISE, never silently truncate the prompt."""
    d = _decoder(tiny)
    try:
        with pytest.raises(PromptTooLong):
            d.generate(LONG, 4, timeout=120)
    finally:
        d.stop()


def _post(port, path, payload, headers=None):
    conn = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        body = json.dumps(payload).encode()
        head = (f"POST {path} HTTP/1.1\r\nHost: x\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n")
        for k, v in (headers or {}).items():
            head += f"{k}: {v}\r\n"
        conn.sendall(head.encode() + b"\r\n" + body)
        conn.settimeout(30)
        data = b""
        while b"\r\n\r\n" not in data:
            data += conn.recv(65536)
        header_blob, _, rest = data.partition(b"\r\n\r\n")
        status = int(header_blob.split(b" ")[1])
        headers_out = {}
        for line in header_blob.split(b"\r\n")[1:]:
            k, _, v = line.decode().partition(":")
            headers_out[k.strip().lower()] = v.strip()
        length = int(headers_out.get("content-length", 0))
        while len(rest) < length:
            rest += conn.recv(65536)
        return status, headers_out, rest[:length]
    finally:
        conn.close()


def test_server_maps_prompt_too_long_to_413():
    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.server import ModelServer

    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=4, max_seq_len=32,
                     max_new_tokens=8, kv_layout="paged",
                     kv_block_size=8, prefill_chunk_tokens=8,
                     max_prompt_len=48),
        port=0, grpc_port=None, batch_timeout_ms=2)
    server.start()
    try:
        port = server.port
        path = "/v1/models/lm-test-tiny:predict"
        status, _h, body = _post(port, path, {
            "instances": [{"tokens": [1] * 48, "max_new_tokens": 2}]})
        assert status == 200, body
        status, _h, body = _post(port, path, {
            "instances": [{"tokens": [1] * 49, "max_new_tokens": 2}]})
        assert status == 413, body
        assert b"prompt" in body.lower()
        # The engine survived the rejection.
        status, _h, _b = _post(port, path, {
            "instances": [{"tokens": [1, 2, 3], "max_new_tokens": 2}]})
        assert status == 200
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Chunk chains x suspension and live weight pushes
# ---------------------------------------------------------------------------


def test_fleet_serves_long_prompts_and_surfaces_413(tiny, wide_greedy):
    """Chunked replicas behind the prefix-affine fleet: long prompts
    route, chunk, and stream byte-identically; a PromptTooLong is the
    REQUEST's fault — it surfaces to the caller without marking the
    replica dead — and the fleet aggregate rolls up chunk counters."""
    from kubeflow_tpu.serving.fleet import DecoderFleet

    fleet = DecoderFleet({"a": _chunked(tiny), "b": _chunked(tiny)})
    try:
        got = [fleet.generate(p, 6, timeout=120)["tokens"]
               for p in PROBES]
        assert got == wide_greedy
        with pytest.raises(PromptTooLong):
            fleet.generate([3] * 113, 4, timeout=120)
        assert fleet.live_members() == ["a", "b"], \
            "a 413 must not kill the replica"
        m = fleet.metrics()
        assert m["prefill_chunks"] > 0
        assert m["prompt_rejected_too_long"] == 1
    finally:
        fleet.stop()


def _two_tier_qos():
    return QosPolicy({"gold": TenantSpec("gold", weight=8, priority=10),
                      "free": TenantSpec("free", weight=1, priority=0)},
                     aging_seconds=30.0)


def test_chunked_gold_suspends_decode_victim_byte_identity(tiny):
    """A long chunked gold admission arrives while a free stream
    decodes in a pool too small for both: the decode victim suspends
    to the host tier across the chunk chain and resumes byte-identical
    to an undisturbed run."""
    def make():
        return _chunked(tiny, chunk=16, max_prompt_len=64,
                        max_new_tokens=32, kv_pool_blocks=13,
                        prefix_cache_slots=4, prefix_cache_min_len=8,
                        qos=_two_tier_qos(), host_kv_bytes=1 << 20,
                        kv_low_watermark=2)

    ref = make()
    try:
        want = ref.generate(SHORT[:8], 24, timeout=120)["tokens"]
    finally:
        ref.stop()
    d = make()
    try:
        h = d.submit(SHORT[:8], 24, tenant="free")
        deadline = time.perf_counter() + 30
        while (len(h._req.out) < 1
               and time.perf_counter() < deadline):
            time.sleep(0.002)
        assert len(h._req.out) >= 1, "victim never started"
        golds = [d.submit(LONG[:64], 4, tenant="gold")
                 for _ in range(2)]
        for g in golds:
            assert len(g.result(timeout=120)["tokens"]) == 4
        out = h.result(timeout=120)["tokens"]
        m = d.metrics()
    finally:
        d.stop()
    assert m["kv_suspends"] >= 1, "scenario failed to suspend"
    assert m["kv_resumes"] >= 1
    assert m["prefill_chunks"] > 0
    assert out == want


def test_mid_chain_slot_never_suspension_victim(tiny, monkeypatch):
    """QoS pressure lands while a free chunked admission is mid-chain:
    the chain's slot holds blocks but is not yet an active stream —
    suspending it would tear half-scattered KV. The picker must skip
    it; the chain completes byte-identical and the golds complete."""
    orig = cont.paged_prefill_chunk

    def slow_chunk(*a, **kw):
        time.sleep(0.05)
        return orig(*a, **kw)

    monkeypatch.setattr(cont, "paged_prefill_chunk", slow_chunk)

    def make():
        return _chunked(tiny, chunk=8, max_prompt_len=64,
                        max_new_tokens=16, qos=_two_tier_qos(),
                        host_kv_bytes=1 << 20, kv_low_watermark=2)

    ref = make()
    try:
        want = ref.generate(LONG[:64], 6, timeout=120)["tokens"]
    finally:
        ref.stop()
    d = make()
    try:
        h = d.submit(LONG[:64], 6, tenant="free")
        deadline = time.perf_counter() + 30
        while (d.metrics()["prefill_chunks"] < 1
               and time.perf_counter() < deadline):
            time.sleep(0.002)
        assert d.metrics()["prefill_chunks"] >= 1, "chain never started"
        golds = [d.submit([9] * 20 + [i], 4, tenant="gold")
                 for i in range(3)]
        for g in golds:
            assert len(g.result(timeout=120)["tokens"]) == 4
        out = h.result(timeout=120)["tokens"]
    finally:
        d.stop()
    assert out == want


def test_weight_swap_mid_chain_restarts_under_new_epoch(
        tiny, tiny_v2, monkeypatch):
    """A live weight push lands between two chunks of one admission:
    the chain must restart from scratch under the new epoch — blocks
    freed, pin released, requeued — so no block mixing both epochs'
    K/V is ever published (or cached). The stream's tokens equal a
    decoder cold-started on the pushed weights."""
    spec, _ = tiny
    cold = ContinuousDecoder(
        tiny_v2, spec.config, slots=4, prefill_len=32,
        max_new_tokens=16, kv_layout="paged", kv_block_size=8,
        prefill_chunk_tokens=8, max_prompt_len=112,
        prefix_cache_slots=4, prefix_cache_min_len=8,
        stream_timeout_s=120.0)
    try:
        want = cold.generate(LONG, 6, timeout=120)["tokens"]
    finally:
        cold.stop()

    orig = cont.paged_prefill_chunk

    def slow_chunk(*a, **kw):
        time.sleep(0.05)
        return orig(*a, **kw)

    monkeypatch.setattr(cont, "paged_prefill_chunk", slow_chunk)
    d = _chunked(tiny, chunk=8, prefix_cache_slots=4,
                 prefix_cache_min_len=8)
    try:
        h = d.submit(LONG, 6)
        deadline = time.perf_counter() + 30
        while (d.metrics()["prefill_chunks"] < 1
               and time.perf_counter() < deadline):
            time.sleep(0.002)
        assert d.metrics()["prefill_chunks"] >= 1, "chain never started"
        d.update_weights(tiny_v2)
        out = h.result(timeout=120)["tokens"]
        # A second admission prefix-hits whatever the first published —
        # it must ALSO be pure new-epoch.
        again = d.generate(LONG, 6, timeout=120)["tokens"]
        m = d.metrics()
    finally:
        d.stop()
    assert m["weights_version"] == 1
    assert out == want, "mid-chain swap published mixed-epoch K/V"
    assert again == want


# ---------------------------------------------------------------------------
# Metrics, exposition, and the deployment surface
# ---------------------------------------------------------------------------


def test_metrics_and_exposition(tiny):
    d = _chunked(tiny, cp_shards=2, chunk=16)
    try:
        d.generate(LONG, 4, timeout=120)
        m = d.metrics()
        text = d.registry.render()
    finally:
        d.stop()
    assert m["prefill_chunks"] > 0
    assert m["prefill_chunk_tokens"] == 16
    assert m["max_prompt_len"] == 112
    assert m["cp_shards"] == 2 and m["pp_stages"] == 1
    assert "serving_prefill_chunks_total" in text
    assert "serving_prefill_chunk_seconds" in text
    assert "serving_cp_shards 2" in text \
        or "serving_cp_shards 2.0" in text
    assert "serving_pp_stages 1" in text \
        or "serving_pp_stages 1.0" in text


def test_chunk_knob_validation(tiny):
    with pytest.raises(ValueError):
        _decoder(tiny, prefill_chunk_tokens=8, kv_layout="dense")
    with pytest.raises(ValueError):
        _decoder(tiny, prefill_chunk_tokens=64)  # > prefill window
    with pytest.raises(ValueError):
        _decoder(tiny, max_prompt_len=112)  # beyond window, no chunks
    with pytest.raises(ValueError):
        _decoder(tiny, max_prompt_len=16)  # below the dense window


def test_tpu_serving_prototype_renders_long_context_flags():
    from kubeflow_tpu.manifests.core import generate

    objs = generate("tpu-serving", {
        "name": "m", "kv_layout": "paged", "prefill_chunk_tokens": 512,
        "max_prompt_len": 32768, "cp_shards": 4, "pp_stages": 2})
    dep = next(o for o in objs if o["kind"] == "Deployment")
    args = dep["spec"]["template"]["spec"]["containers"][0]["args"]
    assert "--prefill-chunk-tokens=512" in args
    assert "--max-prompt-len=32768" in args
    assert "--cp-shards=4" in args
    assert "--pp-stages=2" in args
    # Defaults render NO new args at all (goldens unchanged).
    objs = generate("tpu-serving", {"name": "m"})
    dep = next(o for o in objs if o["kind"] == "Deployment")
    args = dep["spec"]["template"]["spec"]["containers"][0]["args"]
    assert not any(a.startswith(("--prefill-chunk-tokens",
                                 "--max-prompt-len", "--cp-shards",
                                 "--pp-stages")) for a in args)


def test_operator_normalizes_long_context_and_sizes_chips():
    from kubeflow_tpu.operators.inference import (
        InferenceServiceController,
    )

    ctl = InferenceServiceController.__new__(InferenceServiceController)
    svc = {"apiVersion": "kubeflow-tpu.org/v1",
           "kind": "InferenceService",
           "metadata": {"name": "m", "namespace": "kubeflow"},
           "spec": {"model": "m",
                    "engine": {"tpShards": 2, "cpShards": 2,
                               "ppStages": 2, "kv_layout": "paged",
                               "prefillChunkTokens": 256,
                               "maxPromptLen": 8192}}}
    objs = ctl._replica_objects(svc, 0)
    dep = next(o for o in objs if o["kind"] == "Deployment")
    c = dep["spec"]["template"]["spec"]["containers"][0]
    assert "--cp-shards=2" in c["args"]
    assert "--pp-stages=2" in c["args"]
    assert "--prefill-chunk-tokens=256" in c["args"]
    assert "--max-prompt-len=8192" in c["args"]
    # Chips per replica = tp * cp * pp unless pinned.
    assert str(c["resources"]["limits"]["google.com/tpu"]) == "8"


def test_engine_config_and_cli_validation():
    from kubeflow_tpu.serving.__main__ import main as cli_main
    from kubeflow_tpu.serving.engine import EngineConfig

    cfg = EngineConfig()
    assert cfg.prefill_chunk_tokens == 0 and cfg.max_prompt_len == 0
    assert cfg.cp_shards == 1 and cfg.pp_stages == 1
    with pytest.raises(SystemExit):
        cli_main(["--model-name", "lm-test-tiny",
                  "--prefill-chunk-tokens", "8"])  # needs paged
    with pytest.raises(SystemExit):
        cli_main(["--model-name", "lm-test-tiny", "--kv-layout",
                  "paged", "--cp-shards", "2"])  # needs chunking
    with pytest.raises(SystemExit):
        cli_main(["--model-name", "lm-test-tiny", "--kv-layout",
                  "paged", "--max-prompt-len", "4096"])  # needs chunking


def test_handoff_envelope_carries_cp_pp():
    from kubeflow_tpu.serving import handoff as handoff_mod

    env = handoff_mod.pack({
        "tokens": [1, 2], "prefix_len": 2, "block_size": 8,
        "kv_dtype": "fp", "tp_shards": 2, "cp_shards": 4,
        "pp_stages": 2,
        "payload": {"k": __import__("numpy").zeros((1, 2)),
                    "v": __import__("numpy").zeros((1, 2))}})
    assert env["mesh"] == {"tpShards": 2, "cpShards": 4, "ppStages": 2}
    back = handoff_mod.unpack(env)
    assert back["cp_shards"] == 4 and back["pp_stages"] == 2
    # Older envelopes (no cp/pp stamp) unpack as 1.
    del env["mesh"]["cpShards"], env["mesh"]["ppStages"]
    back = handoff_mod.unpack(env)
    assert back["cp_shards"] == 1 and back["pp_stages"] == 1
