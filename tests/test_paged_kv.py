"""Paged KV cache tests: dense/paged byte-identity, zero-copy prefix
sharing (refcounts + CoW), block-leak freedom across retire/error
paths, memory-deferred admission, and the configurable stream timeout.

The contract under test is the serving one: the paged layout changes
WHERE K/V lives (block pool + per-slot tables instead of dense rows),
never WHAT is computed — greedy streams must match the dense layout
byte for byte, cold or warm, plain or chunked or speculative.
"""

import http.client
import time

import jax
import pytest

from kubeflow_tpu.observability.metrics import type_line
from kubeflow_tpu.serving.continuous import (
    ContinuousDecoder,
    StreamHandle,
    _Request,
)
from kubeflow_tpu.serving.engine import EngineConfig
from kubeflow_tpu.serving.kv_allocator import kv_bytes_per_token
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


# ---------------------------------------------------------------------------
# Layout byte-identity (the acceptance bar: paged changes cost, not output)
# ---------------------------------------------------------------------------


def test_dense_paged_greedy_byte_identical(model):
    prompts = [[1, 2, 3], [7, 5], [9, 9, 9, 9, 2], list(range(4, 28))]
    dense = _decoder(model)
    try:
        ref = [dense.generate(p, 6, timeout=120)["tokens"] for p in prompts]
    finally:
        dense.stop()
    paged = _paged(model)
    try:
        for p, r in zip(prompts, ref):
            assert paged.generate(p, 6, timeout=120)["tokens"] == r
        m = paged.metrics()
        assert m["kv_blocks_in_use"] == 0  # drained: every block freed
    finally:
        paged.stop()


def test_dense_paged_sampled_fixed_seed_identical(model):
    """Same seed, temperature>0: the RNG stream is consumed per decode
    round regardless of layout, so sampled outputs match too."""
    prompt = list(range(3, 19))

    def run(layout):
        d = (_paged if layout == "paged" else _decoder)(model, seed=7)
        try:
            return d.generate(prompt, 6, temperature=1.0,
                              timeout=120)["tokens"]
        finally:
            d.stop()

    assert run("paged") == run("dense")


def test_paged_chunked_and_speculative_greedy_parity(model):
    """decode_chunk and verify_chunk ride the same block pool: fused
    chunks and speculative verify must not change paged outputs."""
    prompts = [([3, 17, 29, 3, 17] * 3)[:12], [1, 2, 3]]
    plain = _paged(model)
    try:
        ref = [plain.generate(p, 8, timeout=120)["tokens"] for p in prompts]
    finally:
        plain.stop()
    chunked = _paged(model, chunk_size=4)
    try:
        for p, r in zip(prompts, ref):
            assert chunked.generate(p, 8, timeout=120)["tokens"] == r
    finally:
        chunked.stop()
    spec = _paged(model, speculative_k=3)
    try:
        for p, r in zip(prompts, ref):
            assert spec.generate(p, 8, timeout=120)["tokens"] == r
        assert spec.metrics()["kv_blocks_in_use"] == 0
    finally:
        spec.stop()


def test_paged_eos_parks_and_frees_blocks(model):
    probe = _paged(model)
    try:
        toks = probe.generate([1, 2, 3], 6, timeout=120)["tokens"]
    finally:
        probe.stop()
    eos = toks[2]
    d = _paged(model, eos_id=eos)
    try:
        res = d.generate([1, 2, 3], 6, timeout=120)
        assert res["tokens"] == toks[:3]
        assert res["finish_reason"] == "eos"
        assert d.metrics()["kv_blocks_in_use"] == 0
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# Zero-copy prefix sharing: refcounted full blocks, CoW on partial tails
# ---------------------------------------------------------------------------


def test_warm_hit_block_aligned_shares_with_zero_copies(model):
    """A prefix covering whole blocks is shared purely by refcount:
    shared_blocks climbs, cow_copies stays 0, and the stream matches a
    cache-off decoder byte for byte."""
    donor = list(range(2, 26))            # 24 tokens = 3 full 8-blocks
    warm = donor + [100, 101, 102, 103]   # extends past the donor key
    off = _decoder(model)
    try:
        ref_donor = off.generate(donor, 6, timeout=120)["tokens"]
        ref_warm = off.generate(warm, 6, timeout=120)["tokens"]
    finally:
        off.stop()
    d = _paged(model, prefix_cache_slots=4, prefix_cache_min_len=8)
    try:
        assert d.generate(donor, 6, timeout=120)["tokens"] == ref_donor
        assert d.generate(warm, 6, timeout=120)["tokens"] == ref_warm
        m = d.metrics()
        assert m["prefix_hits"] == 1
        assert m["kv_shared_blocks"] == 3   # all three donor blocks
        assert m["kv_cow_copies"] == 0      # block-aligned: ZERO copies
        assert m["prefix_tokens_reused"] == 24
    finally:
        d.stop()


def test_cow_tail_never_mutates_donor_blocks(model):
    """A hit whose depth lands mid-block CoWs that one block; decoding
    the divergent stream must leave the donor's blocks intact — the
    donor's prompt replays byte-identically afterwards."""
    donor = list(range(2, 22))        # 20 tokens: 2 full blocks + 4 tail
    divergent = donor + [50, 51]
    off = _decoder(model)
    try:
        ref_donor = off.generate(donor, 6, timeout=120)["tokens"]
        ref_div = off.generate(divergent, 6, timeout=120)["tokens"]
    finally:
        off.stop()
    d = _paged(model, prefix_cache_slots=4, prefix_cache_min_len=8)
    try:
        cold = d.generate(donor, 6, timeout=120)["tokens"]
        assert cold == ref_donor
        assert d.generate(divergent, 6, timeout=120)["tokens"] == ref_div
        m = d.metrics()
        assert m["kv_cow_copies"] == 1      # exactly the tail block
        assert m["kv_shared_blocks"] == 2   # the two full blocks
        # Donor's blocks survived the CoW stream: replay is identical
        # (this admission hits the donor entry again and CoWs again).
        assert d.generate(donor, 6, timeout=120)["tokens"] == cold
    finally:
        d.stop()


def test_shared_blocks_visible_in_both_slots_with_refcounts(
        model, monkeypatch):
    """Two in-flight requests over a primed prefix hold the SAME
    physical blocks (trie ref + one per slot) while their owned tail
    blocks stay disjoint — the 'no aliasing unless refcounted-shared'
    invariant, inspected live. Decode steps are throttled so the
    scheduler can't retire the rows before the inspection."""
    import kubeflow_tpu.serving.continuous as cont

    real_step = cont.decode_step

    def slow_step(*a, **kw):
        time.sleep(0.25)
        return real_step(*a, **kw)

    monkeypatch.setattr(cont, "decode_step", slow_step)
    system = list(range(5, 29))  # 24 tokens = 3 blocks, aligned
    d = _paged(model, slots=2, prefix_cache_slots=4,
               prefix_cache_min_len=8)
    try:
        assert d.prime_prefix(system)
        h1 = d.submit(system + [100], 8)
        h2 = d.submit(system + [101], 8)
        it1, it2 = h1.tokens(timeout=120), h2.tokens(timeout=120)
        next(it1), next(it2)  # both admitted and mid-decode
        b0, b1 = d._slot_blocks[0], d._slot_blocks[1]
        shared = set(b0) & set(b1)
        assert len(shared) == 3
        for b in shared:
            # primed entry + two in-flight slots
            assert d._alloc.ref_count(b) == 3
        owned0, owned1 = set(b0) - shared, set(b1) - shared
        assert owned0 and owned1 and not (owned0 & owned1)
        for b in owned0 | owned1:
            assert d._alloc.ref_count(b) == 1
        for it in (it1, it2):
            for _ in it:
                pass
        # Drained: the primed entry holds its 3 blocks, and each
        # finished prompt's publish-on-finish kept one extra tail block
        # alive beyond the donor blocks it re-shares (zero copies, pure
        # refcounts).
        assert d.metrics()["kv_blocks_in_use"] == 5
    finally:
        d.stop()


def test_paged_prime_keeps_sampled_stream_identical(model):
    """prime_prefix writes blocks owned by the trie entry without
    touching the decode RNG: a primed paged decoder samples exactly like
    a cache-off dense decoder with the same seed."""
    system = list(range(3, 23))
    prompt = system + [200, 17, 11]

    def run(cache_on):
        if cache_on:
            d = _paged(model, seed=11, prefix_cache_slots=4,
                       prefix_cache_min_len=8)
        else:
            d = _decoder(model, seed=11)
        try:
            if cache_on:
                assert d.prime_prefix(system)
            return d.generate(prompt, 6, temperature=1.0,
                              timeout=120)["tokens"], d.metrics()
        finally:
            d.stop()

    off, _ = run(False)
    on, m = run(True)
    assert on == off
    assert m["prefix_hits"] == 1


# ---------------------------------------------------------------------------
# Leak freedom: error paths and memory-aware admission
# ---------------------------------------------------------------------------


def test_blocks_freed_after_loop_crash(model, monkeypatch):
    """A decode-loop death frees every block reference — in-flight,
    queued, and popped-but-unregistered admissions included."""
    d = _paged(model, slots=1)
    try:
        inflight = d.submit([1, 2, 3], 8)
        next(inflight.tokens(timeout=60))
        monkeypatch.setattr(
            "kubeflow_tpu.serving.continuous.decode_step",
            lambda *a, **k: (_ for _ in ()).throw(
                RuntimeError("injected decode failure")))
        queued = d.submit([4, 5], 4)
        with pytest.raises(RuntimeError, match="injected decode failure"):
            inflight.result(timeout=10)
        with pytest.raises(RuntimeError, match="injected decode failure"):
            queued.result(timeout=10)
        assert d.metrics()["kv_blocks_in_use"] == 0
    finally:
        d.stop()


def test_memory_deferred_admission_completes_everything(model):
    """A pool holding ONE worst-case sequence serializes admissions by
    memory, not slots: everything still completes FIFO, deferral is
    counted, and the pool drains to zero."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=4, prefill_len=16,
                          max_new_tokens=8, kv_layout="paged",
                          kv_block_size=8, kv_pool_blocks=3)
    try:
        handles = [d.submit([i + 1] * 10, 8) for i in range(5)]
        outs = [h.result(timeout=120)["tokens"] for h in handles]
        assert all(len(o) == 8 for o in outs)
        m = d.metrics()
        assert m["kv_defer_admissions"] > 0
        assert m["peak_in_flight"] == 1  # 10+8 tokens = 3 blocks = pool
        assert m["kv_blocks_in_use"] == 0
    finally:
        d.stop()


def _drained_metrics(model, offered, **kw):
    """Offer ``offered`` two-block requests at once; metrics after all
    of them finished."""
    d = _decoder(model, max_new_tokens=16, stream_timeout_s=300.0, **kw)
    try:
        handles = [d.submit([3 + i % 7] * 6, 10) for i in range(offered)]
        assert all(len(h.result(timeout=300)["tokens"]) == 10
                   for h in handles)
        return d.metrics()
    finally:
        d.stop()


@pytest.mark.parametrize("kv_dtype, at_least", [("fp", 2.0), ("int8", 1.8)])
def test_in_flight_peak_at_equal_pool_bytes(model, kv_dtype, at_least):
    """Admission is bounded by tokens resident, not by rows. The bytes of
    four dense 48-token rows, cut into blocks of 8, hold at least twice
    as many 16-token requests in flight as the dense layout's four; the
    same bytes as int8 blocks hold at least 1.8x the fp blocks' count."""
    fp_blocks = 4 * (32 + 16) // 8
    fp_kw = dict(kv_layout="paged", kv_block_size=8,
                 kv_pool_blocks=fp_blocks)
    if kv_dtype == "fp":
        base = _drained_metrics(model, 16, slots=4)
        more = _drained_metrics(model, 16, slots=16, **fp_kw)
        assert base["peak_in_flight"] == 4
    else:
        # One head of 64: at the preset's head_dim of 16 the scale a
        # position carries per head eats the density int8 buys.
        from kubeflow_tpu.models.registry import get_model

        spec = get_model("lm-test-tiny", n_heads=1, n_kv_heads=1)
        cfg = spec.config
        wide = spec, spec.init(jax.random.PRNGKey(0), cfg)
        bpt = {d: kv_bytes_per_token(
            cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
            jax.numpy.dtype(cfg.dtype).itemsize, d) for d in ("fp", "int8")}
        base = _drained_metrics(wide, 32, slots=32, **fp_kw)
        more = _drained_metrics(
            wide, 32, slots=32, kv_dtype="int8",
            **{**fp_kw,
               "kv_pool_blocks": fp_blocks * bpt["fp"] // bpt["int8"]})
        assert more["kv_bytes_total"] <= base["kv_bytes_total"]
        assert base["peak_in_flight"] == fp_blocks // 2  # memory-bound
        assert base["kv_blocks_in_use"] == 0
    assert more["peak_in_flight"] >= at_least * base["peak_in_flight"]
    assert more["kv_blocks_in_use"] == 0  # drained: every block freed


def test_admission_pressure_reclaims_cached_prefix_blocks(model):
    """Cache-held blocks are reclaimable memory: when a new admission
    needs them, unpinned prefix entries are evicted rather than the
    request deferring forever."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8, kv_layout="paged",
                          kv_block_size=8, kv_pool_blocks=3,
                          prefix_cache_slots=4, prefix_cache_min_len=8)
    try:
        # Finishing publishes the prompt's blocks into the trie, leaving
        # the pool fully claimed by the cache...
        first = d.generate([9] * 10, 8, timeout=120)
        assert d.metrics()["kv_blocks_in_use"] > 0
        # ...which the next admission reclaims by evicting the entry.
        second = d.generate([7] * 10, 8, timeout=120)
        assert len(first["tokens"]) == len(second["tokens"]) == 8
        assert d.metrics()["prefix_evictions"] >= 1
    finally:
        d.stop()


def test_want_zero_pure_prefill_frees_blocks(model):
    d = _paged(model)
    try:
        res = d.generate([5, 6, 7], 0, timeout=120)
        assert res["tokens"] == []
        assert res["prefill_logits"].shape == (256,)
        assert d.metrics()["kv_blocks_in_use"] == 0
    finally:
        d.stop()


def test_block_size_must_divide_total_len(model):
    spec, params = model
    with pytest.raises(ValueError, match="must divide"):
        ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=7, kv_layout="paged",
                          kv_block_size=8)


# ---------------------------------------------------------------------------
# Stream timeout plumbing + Prometheus export
# ---------------------------------------------------------------------------


def test_stream_handle_uses_decoder_default_timeout():
    req = _Request(tokens=[1], want=4, temperature=0.0)
    h = StreamHandle(req, default_timeout=0.05)
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError):
        next(h.tokens())
    with pytest.raises(TimeoutError):
        h.result()
    assert time.perf_counter() - t0 < 5  # not the old hard-coded 60s


def test_decoder_threads_stream_timeout(model):
    """submit() hands the decoder's stream_timeout_s to every handle —
    the one knob replacing the hard-coded 60s."""
    d = _paged(model, stream_timeout_s=123.0)
    try:
        h = d.submit([1], 1)
        assert h._default_timeout == 123.0
        assert len(h.result(timeout=120)["tokens"]) == 1
    finally:
        d.stop()


def test_paged_counters_exported_as_prometheus(model):
    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=4, max_seq_len=16,
                     max_new_tokens=8, kv_layout="paged", kv_block_size=8,
                     prefix_cache_slots=4, prefix_cache_min_len=8),
        port=0, grpc_port=None, batch_timeout_ms=2,
    )
    server.start()
    try:
        prompt = list(range(2, 18))
        for _ in range(2):  # second pass hits (and shares blocks)
            server.handle_predict("lm-test-tiny", {
                "instances": [{"tokens": prompt, "max_new_tokens": 3}],
            })
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        conn.request("GET", "/monitoring/prometheus/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
    finally:
        server.stop()
    assert "serving_kv_blocks_total 12" in text  # 4 slots * 24/8 blocks
    assert "serving_kv_blocks_in_use" in text
    assert type_line("serving_kv_shared_blocks_total", "counter") in text
    assert "serving_kv_cow_copies_total" in text
    assert "serving_kv_defer_admissions_total 0" in text


def test_concurrent_same_round_prefix_hits_stay_exact(model):
    """Regression (found under concurrent shared-prefix traffic): a
    freed slot's block-table row must stay SENTINEL until the slot's
    own admission dispatch. Pointing it at freshly shared blocks at pop
    time let an earlier same-round hit admission's fused decode step
    write through the reassigned row at its stale device length —
    landing junk INSIDE refcount-shared prefix blocks, silently
    corrupting every stream that read the donor prefix afterwards.
    Three followers hitting the same donor concurrently (admitted in
    one round, slots freshly recycled) is the trigger."""
    from concurrent.futures import ThreadPoolExecutor

    prefix = [(7 * j) % 97 + 3 for j in range(24)]
    followers = [prefix + [200, 150 + r, 11 + r, 7] for r in (1, 2, 3)]
    gen = 8

    cold = _paged(model, slots=8, max_new_tokens=gen)
    try:
        ref = [cold.generate(t, gen, timeout=120)["tokens"]
               for t in followers]
    finally:
        cold.stop()

    d = _paged(model, slots=8, max_new_tokens=gen,
               prefix_cache_slots=8, prefix_cache_min_len=16,
               prefill_len_buckets=2, kv_pool_blocks=40,
               stream_timeout_s=120.0)
    try:
        # Leader decodes (recycling slots + publishing the prefix),
        # then all three followers hit the donor in one burst.
        d.generate(prefix + [200, 150, 11, 7], gen, timeout=120)
        with ThreadPoolExecutor(3) as pool:
            out = list(pool.map(
                lambda t: d.generate(t, gen, timeout=120)["tokens"],
                followers))
        m = d.metrics()
    finally:
        d.stop()
    assert m["prefix_hits"] == 3
    assert out == ref  # byte-identical to the no-cache reference
