"""Prefix KV cache + length-bucketed prefill tests.

Covers the host trie (match-through-interior-nodes, LRU eviction order,
in-flight pins), the decoder end-to-end (cold vs warm determinism for
greedy AND fixed-seed sampled decoding, eviction under pool pressure,
suffix-only prefill accounting), the shared ``pow2_bucket`` rule,
``scenarios.percentile``'s nearest-rank rule, and the Prometheus
export of the new counters.
"""

import http.client

import jax
import pytest

from kubeflow_tpu.observability.metrics import type_line
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.engine import EngineConfig, pow2_bucket
from kubeflow_tpu.serving.prefix_cache import PrefixCache
from kubeflow_tpu.serving.scenarios import percentile
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


# ---------------------------------------------------------------------------
# pow2_bucket (shared batch/sequence bucketing rule)
# ---------------------------------------------------------------------------


def test_pow2_bucket_boundaries():
    assert [pow2_bucket(n) for n in (0, 1, 2, 3, 4, 5)] == \
        [1, 1, 2, 4, 4, 8]
    assert pow2_bucket(128) == 128      # max: already a power of two
    assert pow2_bucket(129, cap=128) == 128
    assert pow2_bucket(5, cap=4) == 4


# ---------------------------------------------------------------------------
# scenarios.percentile (nearest rank)
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank():
    # Even length: rank ceil(4*0.5)=2 -> the LOWER middle element (the
    # old int() index read one high).
    assert percentile([1, 2, 3, 4], 50) == 2
    assert percentile([1, 2, 3], 50) == 2
    assert percentile([5], 50) == 5
    assert percentile([5], 99) == 5
    hundred = list(range(1, 101))
    assert percentile(hundred, 50) == 50
    assert percentile(hundred, 99) == 99
    assert percentile(hundred, 100) == 100
    assert percentile(hundred, 1) == 1


# ---------------------------------------------------------------------------
# Host trie: match semantics, LRU, pins
# ---------------------------------------------------------------------------


def test_trie_match_through_interior_nodes():
    """N prompts sharing a system prefix must hit even though the stored
    key diverges after the shared part (causality: rows 0..d-1 depend
    only on tokens 0..d-1)."""
    c = PrefixCache(4, min_len=4)
    shared = list(range(10, 30))
    assert c.reserve(tuple(shared + [1, 2])) is not None
    m = c.match(shared + [3, 4])
    assert m is not None
    entry, depth = m
    assert depth == len(shared)
    assert entry.key[:depth] == tuple(shared)


def test_trie_match_caps_and_min_len():
    c = PrefixCache(4, min_len=4)
    assert c.reserve((1, 2, 3, 4, 5, 6)) is not None
    # Exact re-prompt: capped at len-1 so one suffix token remains.
    _entry, depth = c.match([1, 2, 3, 4, 5, 6])
    assert depth == 5
    # Shorter than min_len: no match even though the path exists.
    assert c.match([1, 2, 3, 4]) is None
    assert c.match([9, 9, 9, 9, 9]) is None
    # reserve of an existing key only touches it.
    assert c.reserve((1, 2, 3, 4, 5, 6)) is None
    assert len(c) == 1


def test_trie_lru_eviction_order():
    c = PrefixCache(2, min_len=1)
    e1 = c.reserve((1,) * 8)
    e2 = c.reserve((2,) * 8)
    assert {e1.slot, e2.slot} == {0, 1}
    c.touch((1,) * 8)                  # e1 becomes MRU
    e3 = c.reserve((3,) * 8)           # evicts e2 (LRU), reuses its slot
    assert c.evictions == 1
    assert e3.slot == e2.slot
    assert c.match(list((2,) * 8) + [0]) is None
    assert c.match(list((1,) * 8) + [0]) is not None


def test_trie_pinned_entries_never_evicted():
    c = PrefixCache(1, min_len=1)
    c.reserve((1, 2, 3, 4))
    entry, _depth = c.match([1, 2, 3, 4, 5])   # pins
    assert c.reserve((7, 8, 9)) is None        # sole slot pinned
    assert c.evictions == 0
    c.release(entry)
    assert c.reserve((7, 8, 9)) is not None    # now evictable
    assert c.evictions == 1
    assert c.match([1, 2, 3, 4, 5]) is None


# ---------------------------------------------------------------------------
# Decoder end-to-end: determinism under reuse
# ---------------------------------------------------------------------------


def test_cold_vs_warm_greedy_byte_identical(model):
    """Same prompt, cache cold then warm (published on finish), must emit
    the identical token stream — and the warm pass must have reused the
    prefix instead of re-prefilling it."""
    prompt = list(range(2, 26))
    d = _decoder(model, prefix_cache_slots=4, prefix_cache_min_len=8,
                 prefill_len_buckets=2)
    try:
        cold = d.generate(prompt, 6, timeout=120)
        warm = d.generate(prompt, 6, timeout=120)
        assert warm["tokens"] == cold["tokens"]
        m = d.metrics()
        assert m["prefix_hits"] == 1
        assert m["prefix_misses"] == 1
        assert m["prefix_tokens_reused"] == len(prompt) - 1
        assert m["prefix_suffix_tokens"] == 1
        assert m["prefill_tokens"] == len(prompt) + 1
    finally:
        d.stop()
    # And both match a cache-off decoder (reuse changes cost, not output).
    d0 = _decoder(model)
    try:
        assert d0.generate(prompt, 6, timeout=120)["tokens"] == \
            cold["tokens"]
    finally:
        d0.stop()


def test_cold_vs_warm_sampled_fixed_seed_identical(model):
    """Fixed-seed sampled decode: a decoder whose cache was primed via
    prime_prefix (which must NOT touch the decode RNG) emits the same
    stream as a cache-off decoder with the same seed."""
    system = list(range(3, 23))
    prompt = system + [200, 17, 11]

    def run(cache_on):
        d = _decoder(model, seed=11,
                     prefix_cache_slots=4 if cache_on else 0,
                     prefix_cache_min_len=8, prefill_len_buckets=2)
        try:
            if cache_on:
                assert d.prime_prefix(system)
            toks = d.generate(prompt, 6, temperature=1.0,
                              timeout=120)["tokens"]
            return toks, d.metrics()
        finally:
            d.stop()

    off, _ = run(False)
    on, m = run(True)
    assert on == off
    assert m["prefix_hits"] == 1
    assert m["prefix_tokens_reused"] == len(system)


def test_want_zero_logits_parity_under_reuse(model):
    """Pure-prefill scoring through a warm cache returns the same
    last-position logits as a cold prefill (within float tolerance)."""
    import numpy as np

    prompt = list(range(4, 24))
    d = _decoder(model, prefix_cache_slots=4, prefix_cache_min_len=8)
    try:
        cold = d.generate(prompt, 0, timeout=120)["prefill_logits"]
        warm = d.generate(prompt, 0, timeout=120)["prefill_logits"]
        assert d.metrics()["prefix_hits"] == 1
        np.testing.assert_allclose(cold, warm, rtol=2e-5, atol=2e-5)
    finally:
        d.stop()


def test_pool_eviction_under_pressure_and_reuse(model):
    """More distinct prefixes than pool slots: LRU evicts, the decoder
    keeps decoding correctly, and a re-submitted evicted prompt simply
    misses (then re-publishes)."""
    d = _decoder(model, prefix_cache_slots=2, prefix_cache_min_len=8)
    try:
        prompts = [[i] * 12 for i in (1, 2, 3)]
        ref = [d.generate(p, 4, timeout=120)["tokens"] for p in prompts]
        m = d.metrics()
        assert m["prefix_inserts"] == 3
        assert m["prefix_evictions"] == 1          # prompt 1 fell out
        assert m["prefix_entries"] == 2
        # Evicted prompt misses (and is re-published); cached one hits.
        assert d.generate(prompts[0], 4, timeout=120)["tokens"] == ref[0]
        assert d.generate(prompts[2], 4, timeout=120)["tokens"] == ref[2]
        m = d.metrics()
        assert m["prefix_hits"] == 1
        assert m["prefix_misses"] == 4
        assert m["prefix_evictions"] == 2
    finally:
        d.stop()


def test_seq_bucketed_prefill_parity(model):
    """prefill_len_buckets changes compiled shapes, never tokens."""
    prompts = [[1, 2, 3], [7, 5], list(range(9, 29))]
    flat = _decoder(model)
    try:
        ref = [flat.generate(p, 5, timeout=120)["tokens"] for p in prompts]
    finally:
        flat.stop()
    bucketed = _decoder(model, prefill_len_buckets=3)
    try:
        for p, r in zip(prompts, ref):
            assert bucketed.generate(p, 5, timeout=120)["tokens"] == r
    finally:
        bucketed.stop()


def test_concurrent_shared_prefix_burst(model):
    """A shared-system-prompt burst: requests sharing a primed system
    prompt all hit, decode correctly, and prefill only suffixes."""
    system = list(range(5, 25))
    d = _decoder(model, prefix_cache_slots=4, prefix_cache_min_len=8)
    try:
        assert d.prime_prefix(system)
        handles = [d.submit(system + [100 + i], 4) for i in range(6)]
        outs = [h.result(timeout=120)["tokens"] for h in handles]
        assert all(len(o) == 4 for o in outs)
        m = d.metrics()
        assert m["prefix_hits"] == 6
        assert m["prefix_tokens_reused"] == 6 * len(system)
        assert m["prefix_suffix_tokens"] == 6
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# Prometheus export of the new counters
# ---------------------------------------------------------------------------


def test_prefix_counters_exported_as_prometheus(model):
    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=4, max_seq_len=32,
                     max_new_tokens=8, prefix_cache_slots=4,
                     prefix_cache_min_len=8, prefill_len_buckets=2),
        port=0, grpc_port=None, batch_timeout_ms=2,
    )
    server.start()
    try:
        prompt = list(range(2, 22))
        for _ in range(2):  # second pass hits the cache
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
    assert (type_line("serving_prefix_hits_total", "counter")
            + "serving_prefix_hits_total 1\n") in text
    assert "serving_prefix_tokens_reused_total 19" in text
    assert type_line("serving_prefix_entries", "gauge") in text
    assert "serving_prefill_dispatches_total" in text
    assert "serving_prefill_tokens_total" in text


def test_collector_helper_renders_types():
    from kubeflow_tpu.observability.collector import render_prometheus

    text = render_prometheus({"x_total": 3, "y": 1.5})
    assert text == (type_line("x_total", "counter") + "x_total 3\n"
                    + type_line("y", "gauge") + "y 1.500000\n")
