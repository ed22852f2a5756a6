"""Int8 quantized KV blocks + fused block-table attention.

Contract under test: ``kv_dtype="int8"`` changes what a resident KV
byte buys (payload + per-position per-head scales instead of fp
elements), never the serving semantics — allocator share/free/CoW
invariants hold with scale arrays riding the same block ids, leak
checks cover the scale pool (it IS the same pool bookkeeping), and
greedy streams stay within quantization tolerance of the fp reference.
``kv_fused`` changes where the paged read happens (block-walking kernel
vs materialized gather), never what is computed: the op-level paths are
pinned against the dense reference, and the compiled decode step must
not trace a gather at all.
"""

import http.client

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.observability.metrics import type_line
import kubeflow_tpu.models.decode as decode_mod
from kubeflow_tpu.ops.attention import (
    paged_decode_attention,
    paged_span_attention,
)
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.engine import EngineConfig
from kubeflow_tpu.serving.kv_allocator import (
    BlockAllocator,
    kv_bytes_per_token,
)
from kubeflow_tpu.serving.server import ModelServer


@pytest.fixture(scope="module")
def model():
    from kubeflow_tpu.models.registry import get_model

    spec = get_model("lm-test-tiny")
    params = spec.init(jax.random.PRNGKey(0), spec.config)
    return spec, params


def _decoder(model, **kw):
    spec, params = model
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_len", 32)
    kw.setdefault("max_new_tokens", 8)
    return ContinuousDecoder(params, spec.config, **kw)


def _paged(model, **kw):
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 8)
    return _decoder(model, **kw)


def _agreement(a, b):
    return sum(x == y for s, t in zip(a, b) for x, y in zip(s, t)) / max(
        sum(len(s) for s in a), 1)


PROMPTS = [[1, 2, 3], [7, 5], [9, 9, 9, 9, 2], list(range(4, 20))]


# ---------------------------------------------------------------------------
# Op level: fused paths vs the dense gather reference
# ---------------------------------------------------------------------------


def _ref_attention(q, kp, vp, table, pos, n):
    b, mb = table.shape
    bs, hkv, hd = kp.shape[1], kp.shape[2], kp.shape[3]
    g = q.shape[1] // hkv
    k = kp[jnp.clip(table, 0, n - 1)].reshape(b, mb * bs, hkv, hd)
    v = vp[jnp.clip(table, 0, n - 1)].reshape(b, mb * bs, hkv, hd)
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32))
    s = s * (hd ** -0.5)
    mask = jnp.arange(mb * bs)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", p,
                      v.astype(jnp.float32)).reshape(b, q.shape[1], hd)


def _rand_pools(quant: bool):
    rng = np.random.RandomState(7)
    n, bs, hkv, g, hd, b, mb = 9, 8, 2, 2, 16, 3, 4
    q = jnp.asarray(rng.randn(b, hkv * g, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(n, bs, hkv, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(n, bs, hkv, hd).astype(np.float32))
    table = np.full((b, mb), n, np.int32)
    table[0, :3] = [2, 5, 1]
    table[1, :2] = [0, 7]
    table[2, :4] = [3, 4, 6, 8]
    pos = jnp.asarray([17, 9, 31], np.int32)
    if quant:
        kp = decode_mod._quantize_kv(kp)
        vp = decode_mod._quantize_kv(vp)
    return q, kp, vp, jnp.asarray(table), pos, n, hkv


def test_fused_xla_matches_gather_reference():
    q, kp, vp, table, pos, n, hkv = _rand_pools(quant=False)
    ref = _ref_attention(q, kp, vp, table, pos, n)
    out = paged_decode_attention(q, kp, vp, table, pos, n_kv_heads=hkv,
                                 implementation="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pallas_kernel_matches_xla_walk():
    """The TPU kernel (interpret mode off-TPU) and the XLA block walk
    are the same algorithm: identical masking, identical accumulation
    — fp and int8, sentinel rows included."""
    for quant in (False, True):
        q, kp, vp, table, pos, n, hkv = _rand_pools(quant=quant)
        xla = paged_decode_attention(q, kp, vp, table, pos,
                                     n_kv_heads=hkv, implementation="xla")
        pal = paged_decode_attention(q, kp, vp, table, pos,
                                     n_kv_heads=hkv,
                                     implementation="pallas",
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(xla),
                                   rtol=1e-6, atol=1e-6)


def _ref_span_attention(q, kp, vp, table, pos, n):
    """Dense gather reference for the S-wide span read: token ``s`` of
    row ``b`` attends virtual positions ``<= pos[b] + s``."""
    b, s_w = q.shape[0], q.shape[1]
    mb = table.shape[1]
    bs, hkv, hd = kp.shape[1], kp.shape[2], kp.shape[3]
    g = q.shape[2] // hkv
    k = kp[jnp.clip(table, 0, n - 1)].reshape(b, mb * bs, hkv, hd)
    v = vp[jnp.clip(table, 0, n - 1)].reshape(b, mb * bs, hkv, hd)
    qg = q.reshape(b, s_w, hkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                        k.astype(jnp.float32)) * (hd ** -0.5)
    cols = pos[:, None] + jnp.arange(s_w)[None, :]
    mask = jnp.arange(mb * bs)[None, None, :] <= cols[:, :, None]
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(b, s_w, q.shape[2], hd)


def test_span_fused_matches_gather_reference():
    """The span block-walk (verify scoring / suffix prefill's fused
    read) is pinned to the dense gather reference — fp AND int8,
    sentinel table entries included."""
    for quant in (False, True):
        q1, kp, vp, table, pos, n, hkv = _rand_pools(quant=quant)
        rng = np.random.RandomState(11)
        s_w = 4
        q = jnp.asarray(rng.randn(q1.shape[0], s_w, q1.shape[1],
                                  q1.shape[2]).astype(np.float32))
        if quant:
            deq_k = kp["q"].astype(jnp.float32) * kp["scale"][..., None]
            deq_v = vp["q"].astype(jnp.float32) * vp["scale"][..., None]
        else:
            deq_k, deq_v = kp, vp
        ref = _ref_span_attention(q, deq_k, deq_v, table, pos, n)
        out = paged_span_attention(q, kp, vp, table, pos, n_kv_heads=hkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_span_fused_decode_step_parity():
    """A decode step IS a width-1 span: paged_span_attention at S=1
    must agree with paged_decode_attention on the same pools."""
    q, kp, vp, table, pos, n, hkv = _rand_pools(quant=False)
    dec = paged_decode_attention(q, kp, vp, table, pos, n_kv_heads=hkv,
                                 implementation="xla")
    span = paged_span_attention(q[:, None], kp, vp, table, pos,
                                n_kv_heads=hkv)[:, 0]
    np.testing.assert_allclose(np.asarray(span), np.asarray(dec),
                               rtol=1e-6, atol=1e-6)


def test_fused_speculative_and_prefix_admission_trace_no_gather(
        model, monkeypatch):
    """ROADMAP item-4 leftover closed: with kv_fused on, the SPAN-wide
    reads (verify scoring, suffix prefill) ride the block walk too — a
    fused decoder running speculation + prefix hits traces ZERO dense
    gathers, stays within the pinned tolerance of the gather reference,
    and leaks nothing."""
    donor = list(range(2, 22))
    spec_prompts = [([3, 17, 29, 3, 17] * 3)[:12], [1, 2, 3]]
    kw = dict(speculative_k=3, prefix_cache_slots=4,
              prefix_cache_min_len=8)
    plain = _paged(model, **kw)
    try:
        ref = [plain.generate(p, 6, timeout=120)["tokens"]
               for p in spec_prompts]
        ref_cold = plain.generate(donor, 6, timeout=120)["tokens"]
        ref_hit = plain.generate(donor + [50, 51], 6,
                                 timeout=120)["tokens"]
        assert plain.metrics()["prefix_hits"] == 1
    finally:
        plain.stop()

    calls = {"n": 0}
    real = decode_mod._pool_gather

    def counting(*a, **kws):
        calls["n"] += 1
        return real(*a, **kws)

    monkeypatch.setattr(decode_mod, "_pool_gather", counting)
    fused = _paged(model, kv_fused=True, **kw)
    try:
        out = [fused.generate(p, 6, timeout=120)["tokens"]
               for p in spec_prompts]
        out_cold = fused.generate(donor, 6, timeout=120)["tokens"]
        out_hit = fused.generate(donor + [50, 51], 6,
                                 timeout=120)["tokens"]
        m = fused.metrics()
    finally:
        fused.stop()
    assert calls["n"] == 0  # no span OR decode read materialized
    assert m["prefix_hits"] == 1  # the suffix-prefill path really ran
    assert m["spec_verify_dispatches"] > 0  # the verify path really ran
    assert _agreement(out + [out_cold, out_hit],
                      ref + [ref_cold, ref_hit]) >= 0.75
    assert all(not blocks for blocks in fused._slot_blocks)


def test_int8_dequant_within_quantization_error():
    """Write → gather roundtrip error is bounded by the abs-max step:
    |x - dq(q(x))| <= amax/254 per (position, head) vector."""
    rng = np.random.RandomState(3)
    vals = jnp.asarray(rng.randn(2, 5, 3, 16).astype(np.float32))
    qd = decode_mod._quantize_kv(vals)
    assert qd["q"].dtype == jnp.int8
    deq = qd["q"].astype(jnp.float32) * qd["scale"][..., None]
    amax = np.max(np.abs(np.asarray(vals)), axis=-1, keepdims=True)
    err = np.abs(np.asarray(deq) - np.asarray(vals))
    assert (err <= amax / 254 + 1e-7).all()
    # All-zero vectors quantize to exact zeros (scale 0, not NaN).
    zq = decode_mod._quantize_kv(jnp.zeros((1, 2, 2, 8)))
    assert not np.isnan(np.asarray(zq["scale"])).any()
    assert (np.asarray(zq["q"]) == 0).all()


def test_copy_block_carries_scales():
    """The CoW device copy moves payload AND scales in one dispatch —
    a copied block dequantizes to exactly the donor's values, and
    mutating the copy never touches the donor (the allocator's
    'no aliasing unless refcounted' invariant, scale pool included)."""
    rng = np.random.RandomState(5)
    lyr, n, bs, h, hd = 2, 4, 8, 2, 16
    vals = jnp.asarray(rng.randn(lyr, n, bs, h, hd).astype(np.float32))
    qd = decode_mod._quantize_kv(vals)
    qv = decode_mod._quantize_kv(vals * 2.0)
    # Snapshot before the call: copy_block donates the pool buffers.
    expect = {"k": jax.tree.map(np.asarray, qd),
              "v": jax.tree.map(np.asarray, qv)}
    pool = {"k": {"q": qd["q"], "scale": qd["scale"]},
            "v": {"q": qv["q"], "scale": qv["scale"]}}
    pool2 = decode_mod.copy_block(pool, jnp.int32(3), jnp.int32(1))
    for side in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(pool2[side]["q"][:, 3]),
                                      expect[side]["q"][:, 1])
        np.testing.assert_array_equal(
            np.asarray(pool2[side]["scale"][:, 3]),
            expect[side]["scale"][:, 1])
    # Overwrite the copy (layer 0 of the whole pool); the donor block
    # must be untouched.
    table = jnp.asarray(np.array([[3]], np.int32))
    new = jnp.asarray(rng.randn(1, 1, h, hd).astype(np.float32))
    k3 = decode_mod._pool_write(pool2["k"], 0, table,
                                jnp.zeros((1, 1), jnp.int32), new)
    np.testing.assert_array_equal(np.asarray(k3["q"][0, 1]),
                                  expect["k"]["q"][0, 1])
    np.testing.assert_array_equal(np.asarray(k3["scale"][0, 1]),
                                  expect["k"]["scale"][0, 1])
    assert not np.array_equal(np.asarray(k3["q"][0, 3]),
                              expect["k"]["q"][0, 1])  # copy did change


# ---------------------------------------------------------------------------
# Decoder level: tolerance parity, sharing/CoW with scales, leak freedom
# ---------------------------------------------------------------------------


def test_int8_greedy_within_tolerance_and_leak_free(model):
    fp = _paged(model)
    try:
        ref = [fp.generate(p, 6, timeout=120)["tokens"] for p in PROMPTS]
    finally:
        fp.stop()
    q8 = _paged(model, kv_dtype="int8")
    try:
        out = [q8.generate(p, 6, timeout=120)["tokens"] for p in PROMPTS]
        m = q8.metrics()
    finally:
        q8.stop()
    assert _agreement(out, ref) >= 0.75
    assert all(o[0] == r[0] for o, r in zip(out, ref))  # first tokens
    assert m["kv_blocks_in_use"] == 0  # leak check covers scale pool too
    assert m["kv_dtype"] == "int8"


def test_fused_decode_within_tolerance_and_no_gather_traced(
        model, monkeypatch):
    """kv_fused must (a) stay within tolerance of the gather reference
    and (b) never trace _pool_gather into the compiled decode path —
    tracing is when XLA would bake the dense [slots, total_len] view
    into the executable."""
    plain = _paged(model)
    try:
        ref = [plain.generate(p, 6, timeout=120)["tokens"]
               for p in PROMPTS]
    finally:
        plain.stop()
    calls = {"n": 0}
    real = decode_mod._pool_gather

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(decode_mod, "_pool_gather", counting)
    fused = _paged(model, kv_fused=True)
    try:
        out = [fused.generate(p, 6, timeout=120)["tokens"]
               for p in PROMPTS]
        m = fused.metrics()
    finally:
        fused.stop()
    assert _agreement(out, ref) >= 0.75
    assert calls["n"] == 0
    assert m["kv_blocks_in_use"] == 0
    assert m["kv_fused"] is True


def test_int8_prefix_share_and_cow_keep_donor_exact(model):
    """Zero-copy sharing with scale blocks riding along: a hit maps the
    donor's quantized blocks by refcount, the CoW'd tail copies payload
    + scales, and decoding the divergent stream leaves the donor's
    blocks intact — its prompt replays exactly as it first decoded."""
    donor = list(range(2, 22))        # 20 tokens: 2 full blocks + 4 tail
    divergent = donor + [50, 51]
    d = _paged(model, kv_dtype="int8", prefix_cache_slots=4,
               prefix_cache_min_len=8)
    try:
        cold = d.generate(donor, 6, timeout=120)["tokens"]
        d.generate(divergent, 6, timeout=120)
        m = d.metrics()
        assert m["prefix_hits"] == 1
        assert m["kv_shared_blocks"] == 2
        assert m["kv_cow_copies"] == 1
        # Donor blocks survived the CoW stream: the replay hits the
        # donor entry again and reads the SAME quantized values, so the
        # stream is bit-identical to the cold run.
        assert d.generate(donor, 6, timeout=120)["tokens"] == cold
        # Only CACHE-held references remain (prefix entries keep their
        # blocks alive for future hits); no slot leaked anything.
        assert d.metrics()["kv_blocks_in_use"] > 0
        assert all(not blocks for blocks in d._slot_blocks)
    finally:
        d.stop()


def test_int8_speculative_and_chunked_complete_leak_free(model):
    """verify_chunk and decode_chunk ride the quantized pool (and the
    fused read) without leaking blocks or hanging rows."""
    prompts = [([3, 17, 29, 3, 17] * 3)[:12], [1, 2, 3]]
    for kw in (dict(chunk_size=4), dict(speculative_k=3),
               dict(chunk_size=4, kv_fused=True)):
        d = _paged(model, kv_dtype="int8", **kw)
        try:
            for p in prompts:
                assert len(d.generate(p, 8, timeout=120)["tokens"]) == 8
            assert d.metrics()["kv_blocks_in_use"] == 0
        finally:
            d.stop()


def test_int8_prime_prefix_quantizes_into_entry_blocks(model):
    system = list(range(3, 23))
    d = _paged(model, kv_dtype="int8", prefix_cache_slots=4,
               prefix_cache_min_len=8)
    try:
        assert d.prime_prefix(system)
        res = d.generate(system + [200, 17, 11], 6, timeout=120)
        assert len(res["tokens"]) == 6
        m = d.metrics()
        assert m["prefix_hits"] == 1
        assert m["kv_shared_blocks"] > 0
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# Knob validation + byte accounting + Prometheus export
# ---------------------------------------------------------------------------


def test_kv_dtype_requires_paged(model):
    with pytest.raises(ValueError, match="requires kv_layout"):
        _decoder(model, kv_dtype="int8")
    with pytest.raises(ValueError, match="requires kv_layout"):
        _decoder(model, kv_fused=True)
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        _paged(model, kv_dtype="int4")


def test_cli_rejects_non_paged_int8_and_fused():
    from kubeflow_tpu.serving.__main__ import main

    for extra in (["--kv-dtype", "int8"], ["--kv-fused-attention"]):
        with pytest.raises(SystemExit) as e:
            main(["--model-name", "lm-test-tiny", *extra])
        assert e.value.code == 2


def test_kv_bytes_per_token_formula():
    # fp: 2 * L * Hkv * hd * itemsize; int8: 2 * L * Hkv * (hd + 4).
    assert kv_bytes_per_token(2, 2, 16, 2, "fp") == 256
    assert kv_bytes_per_token(2, 2, 16, 2, "int8") == 160
    assert kv_bytes_per_token(16, 8, 128, 2, "fp") == 65536
    assert kv_bytes_per_token(16, 8, 128, 2, "int8") == 33792
    with pytest.raises(ValueError):
        kv_bytes_per_token(1, 1, 1, 1, "fp8")


def test_allocator_prices_bytes():
    a = BlockAllocator(4, block_size=8, bytes_per_token=10)
    assert a.bytes_total == 4 * 8 * 10
    assert a.bytes_in_use == 0
    got = a.alloc(3)
    assert a.bytes_in_use == 3 * 8 * 10
    a.share(got[0])
    assert a.bytes_in_use == 3 * 8 * 10  # refcounts don't double-bill
    for b in got:
        a.free(b)
    a.free(got[0])
    assert a.bytes_in_use == 0


def test_int8_metrics_and_prometheus_gauges(model):
    d = _paged(model, kv_dtype="int8")
    try:
        m = d.metrics()
        spec, _ = model
        cfg = spec.config
        want = kv_bytes_per_token(cfg.n_layers, cfg.n_kv_heads,
                                  cfg.head_dim,
                                  jnp.dtype(cfg.dtype).itemsize, "int8")
        assert m["kv_bytes_per_token"] == want
        assert m["kv_bytes_total"] == m["kv_blocks_total"] * 8 * want
    finally:
        d.stop()
    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=4, max_seq_len=16,
                     max_new_tokens=8, kv_layout="paged", kv_block_size=8,
                     kv_dtype="int8"),
        port=0, grpc_port=None, batch_timeout_ms=2,
    )
    server.start()
    try:
        server.handle_predict("lm-test-tiny", {
            "instances": [{"tokens": [1, 2, 3], "max_new_tokens": 2}],
        })
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        conn.request("GET", "/monitoring/prometheus/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
    finally:
        server.stop()
    assert "serving_kv_dtype_int8 1" in text
    assert "serving_sparse_attn_pallas 0" in text  # no sparse layer here
    assert f"serving_kv_bytes_per_token {want}" in text
    assert type_line("serving_kv_bytes_in_use", "gauge") in text
    assert "serving_kv_bytes_total" in text
