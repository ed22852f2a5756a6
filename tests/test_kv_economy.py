"""Fleet KV economy tests: the prefix→holder directory, the cold
content-addressed store, and the decoder's fleet miss path
(trie → host → peer → cold → prefill).

The churn contracts under test are the ones that keep the economy
safe, not just fast:

- a holder dying MID-import degrades to the cold tier or a plain
  prefill — counted, never a hang, never wrong bytes;
- a weight push landing MID-pull makes the in-flight envelope stale
  and it is REFUSED (``kv_import_stale_refused``), not installed as
  garbage KV;
- the recompute-vs-import crossover skips pulls that would not save
  enough prefill to pay for themselves;
- all four tiers drain with zero leaked blocks.
"""

import jax
import pytest

from kubeflow_tpu.serving.affinity import prefix_affinity_key
from kubeflow_tpu.serving.cold_store import (
    ColdKvStore,
    cold_store_from_ref,
    content_key,
)
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.fleet import DecoderFleet
from kubeflow_tpu.serving.kv_directory import COLD_HOLDER, KvDirectory


@pytest.fixture(scope="module")
def model():
    from kubeflow_tpu.models.registry import get_model

    spec = get_model("lm-test-tiny")
    params = spec.init(jax.random.PRNGKey(0), spec.config)
    return spec, params


AFFINITY = 16
# Prompt family sharing the affinity window (first 16 token ids): the
# directory keys on that window, so peers only find each other when
# their prompts agree on it.
BASE = [(3 * j) % 89 + 2 for j in range(20)]


def _economy(model, name, directory, cold=None, fetch=None, **kw):
    spec, params = model
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_len", 32)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("prefix_cache_slots", 4)
    kw.setdefault("prefix_cache_min_len", 4)
    return ContinuousDecoder(
        params, spec.config, kv_directory=directory, cold_store=cold,
        peer_fetch=fetch, kv_affinity_tokens=AFFINITY,
        replica_name=name, **kw)


def _plain(model, **kw):
    spec, params = model
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_len", 32)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 8)
    return ContinuousDecoder(params, spec.config, **kw)


# ---------------------------------------------------------------------------
# Directory unit contracts
# ---------------------------------------------------------------------------


def test_directory_deepens_same_epoch_replaces_on_epoch_change():
    d = KvDirectory(capacity=4)
    d.publish("k", "a", prefix_len=8, version=1, tier="hbm")
    d.publish("k", "a", prefix_len=4, version=1, tier="host")
    # Same epoch: a shallower re-publish never shrinks the claim.
    assert d.lookup("k")[0].prefix_len == 8
    d.publish("k", "a", prefix_len=2, version=2, tier="hbm")
    # Epoch change: the old depth is no longer evidence.
    assert d.lookup("k")[0].prefix_len == 2
    assert d.lookup("k", version=1) == []


def test_directory_lookup_deepest_first_with_filters():
    d = KvDirectory()
    d.publish("k", "a", prefix_len=4, version=1)
    d.publish("k", "b", prefix_len=16, version=1)
    d.publish("k", COLD_HOLDER, prefix_len=24, version=1, tier="cold")
    assert [h.holder for h in d.lookup("k")] == [COLD_HOLDER, "b", "a"]
    assert [h.holder for h in d.lookup("k", exclude=("b", COLD_HOLDER))] \
        == ["a"]
    # holders() is the gateway view: warm names only.
    assert d.holders("k") == ["b", "a"]


def test_directory_withdraw_drop_holder_and_lru_eviction():
    d = KvDirectory(capacity=2)
    d.publish("k1", "a", prefix_len=4, version=1)
    d.publish("k1", "b", prefix_len=4, version=1)
    d.publish("k2", "a", prefix_len=4, version=1)
    d.withdraw("k1", "b")
    assert d.holders("k1") == ["a"]
    d.drop_holder("a")  # replica death sweeps every key
    assert d.holders("k1") == [] and d.holders("k2") == []
    d.publish("k3", "c", prefix_len=1, version=1)
    d.publish("k4", "c", prefix_len=1, version=1)
    d.publish("k5", "c", prefix_len=1, version=1)  # evicts the LRU key
    assert len(d) == 2
    assert d.stats()["evictions"] == 1


# ---------------------------------------------------------------------------
# Cold store unit contracts
# ---------------------------------------------------------------------------


def _fake_handoff(tokens, *, block_size=8):
    import numpy as np

    return {"tokens": list(tokens), "prefix_len": len(tokens),
            "block_size": block_size, "kv_dtype": "fp",
            "tp_shards": 1, "cp_shards": 1, "pp_stages": 1,
            "payload": {"k": np.zeros((2, 3), dtype=np.float32),
                        "v": np.zeros((2, 3), dtype=np.float32)}}


def test_cold_store_epoch_in_key_makes_stale_unreachable():
    store = ColdKvStore(1 << 20)
    toks = list(range(1, 9))
    assert content_key(toks, 1) != content_key(toks, 2)
    store.put(_fake_handoff(toks), version=1)
    assert store.match(toks + [99], version=2) is None  # new epoch
    got = store.match(toks + [99], version=1)
    assert got is not None and got[1] == 8
    # Interior match: a shorter probe still finds the stored prefix,
    # capped at len - 1 so one suffix token remains to prefill.
    assert store.peek_depth(toks[:5], version=1) == 4


def test_cold_store_dedup_and_byte_lru():
    store = ColdKvStore(1 << 20)
    k1 = store.put(_fake_handoff([1, 2, 3]), version=7)
    k2 = store.put(_fake_handoff([1, 2, 3]), version=7)
    assert k1 == k2 and len(store) == 1 and store.stats()["puts"] == 1
    one = store.stats()["bytes_in_use"]
    tiny = ColdKvStore(int(one * 2.5))
    tiny.put(_fake_handoff([1, 2, 3]), version=7)
    tiny.put(_fake_handoff([4, 5, 6]), version=7)
    tiny.put(_fake_handoff([7, 8, 9]), version=7)  # evicts the oldest
    assert tiny.stats()["evictions"] >= 1
    assert tiny.stats()["bytes_in_use"] <= tiny.capacity_bytes
    assert tiny.match([1, 2, 3, 0], version=7) is None


def test_cold_store_ref_registry():
    a = cold_store_from_ref("mem://t-econ-reg?bytes=4096")
    b = cold_store_from_ref("mem://t-econ-reg?bytes=9999")
    assert a is b  # first resolver fixes capacity; the name is shared
    assert a.capacity_bytes == 4096
    assert cold_store_from_ref("") is None
    with pytest.raises(ValueError):
        cold_store_from_ref("s3://bucket/kv")


# ---------------------------------------------------------------------------
# The fleet miss path (peer / cold import) end to end
# ---------------------------------------------------------------------------


def test_peer_import_byte_identical_and_saves_prefill(model):
    """Replica b misses locally, finds a's directory hint, pulls the
    prefix over the handoff envelope, and prefills only the tail —
    byte-identical to a cold decoder at a fraction of the prefill."""
    p1 = BASE + [40]
    p2 = BASE + [51, 52, 53]
    plain = _plain(model)
    try:
        ref = plain.generate(p2, 6, timeout=120)["tokens"]
    finally:
        plain.stop()

    directory = KvDirectory()
    a = _economy(model, "a", directory)
    b = _economy(model, "b", directory)
    fleet = DecoderFleet({"a": a, "b": b}, affinity_tokens=AFFINITY)
    try:
        a.generate(p1, 6, timeout=120)
        assert directory.holders(prefix_affinity_key(p1, AFFINITY))
        got = b.generate(p2, 6, timeout=120)["tokens"]
        assert got == ref
        mb = b.metrics()
        assert mb["kv_peer_hits"] == 1
        assert mb["kv_peer_import_bytes"] > 0
        assert mb["prefill_tokens"] < len(p2)  # only the tail
        ma = a.metrics()
        assert ma["kv_handoff_exports"] == 1
        # Steady state: re-running the same prompt (now a trie hit)
        # must not grow the pool — imported blocks are refcounted and
        # released exactly like locally prefilled ones.
        held = mb["kv_blocks_in_use"]
        b.generate(p2, 6, timeout=120)
        assert b.metrics()["kv_blocks_in_use"] == held
        # Exporter and importer alike: what is still held is the tries'.
        for rep in (a, b):
            assert all(not blks for blks in rep._slot_blocks)
    finally:
        fleet.stop()


def test_holder_dies_mid_import_falls_back_to_prefill_never_hangs(model):
    """The hint names a holder that dies between lookup and pull: the
    probe costs one counted failure and a withdrawn hint, and the
    request completes via its own prefill — exact bytes, no hang."""
    p1 = BASE + [40]
    p2 = BASE + [51, 52, 53]
    plain = _plain(model)
    try:
        ref = plain.generate(p2, 6, timeout=120)["tokens"]
    finally:
        plain.stop()

    directory = KvDirectory()
    a = _economy(model, "a", directory)
    b = _economy(model, "b", directory)
    fleet = DecoderFleet({"a": a, "b": b}, affinity_tokens=AFFINITY)
    inner = fleet._peer_fetch

    def dying_fetch(holder, tokens, version):
        fleet.mark_dead(holder)  # death lands mid-import
        return inner(holder, tokens, version)

    b._peer_fetch = dying_fetch
    try:
        a.generate(p1, 6, timeout=120)
        got = b.generate(p2, 6, timeout=120)["tokens"]
        assert got == ref
        mb = b.metrics()
        assert mb["kv_peer_fetch_failures"] == 1
        assert mb["kv_peer_hits"] == 0
        # mark_dead swept a's hints (b, having now served the prompt
        # itself, advertises its own copy — that one is fresh).
        assert "a" not in directory.holders(
            prefix_affinity_key(p2, AFFINITY))
    finally:
        fleet.stop()


def test_holder_death_falls_back_to_cold_tier(model):
    """Same death, but the prefix was demoted to the shared cold store
    first: the miss path falls PAST the dead peer into the cold tier
    and still imports exact bytes instead of recomputing."""
    p1 = BASE + [40]
    p2 = BASE + [51, 52, 53]
    directory = KvDirectory()
    cold = ColdKvStore(8 << 20)
    a = _economy(model, "a", directory, cold=cold)
    b = _economy(model, "b", directory, cold=cold)
    fleet = DecoderFleet({"a": a, "b": b}, affinity_tokens=AFFINITY)

    def dead_fetch(holder, tokens, version):
        return None  # every peer pull fails — holder is gone

    b._peer_fetch = dead_fetch
    plain = _plain(model)
    try:
        ref = plain.generate(p2, 6, timeout=120)["tokens"]
    finally:
        plain.stop()
    try:
        a.generate(p1, 6, timeout=120)
        # Park a's cached prefix in the cold tier (the demotion hook's
        # payload, driven directly so the test does not depend on
        # host-tier pressure mechanics).
        h = a.export_prefix(p2)
        ver = h.pop("weights_version")
        assert cold.put(h, version=ver) is not None
        got = b.generate(p2, 6, timeout=120)["tokens"]
        assert got == ref
        mb = b.metrics()
        assert mb["kv_cold_hits"] == 1
        assert mb["kv_cold_import_bytes"] > 0
        assert mb["kv_peer_fetch_failures"] == 1  # the dead peer probe
    finally:
        fleet.stop()


def test_epoch_bump_mid_pull_refuses_stale_envelope(model):
    """A live weight push lands while the envelope is in flight: the
    import re-reads the epoch under the state lock and REFUSES the
    stale bytes — counted, and the stream still matches a cold decode
    under the new (identical) weights. Never garbage KV."""
    spec, params = model
    p1 = BASE + [40]
    p2 = BASE + [51, 52, 53]
    plain = _plain(model)
    try:
        ref = plain.generate(p2, 6, timeout=120)["tokens"]
    finally:
        plain.stop()

    directory = KvDirectory()
    a = _economy(model, "a", directory)
    b = _economy(model, "b", directory)
    fleet = DecoderFleet({"a": a, "b": b}, affinity_tokens=AFFINITY)
    inner = fleet._peer_fetch

    def racing_fetch(holder, tokens, version):
        got = inner(holder, tokens, version)
        # The push lands after the fetch, before the install: the same
        # params under a new epoch, so outputs stay comparable while
        # the envelope's stamp goes stale.
        b.update_weights(params)
        return got

    b._peer_fetch = racing_fetch
    try:
        a.generate(p1, 6, timeout=120)
        got = b.generate(p2, 6, timeout=120)["tokens"]
        assert got == ref
        mb = b.metrics()
        assert mb["kv_import_stale_refused"] == 1
        assert mb["kv_peer_hits"] == 0
    finally:
        fleet.stop()


def test_crossover_skips_shallow_remote_prefix(model):
    """The recompute-vs-import crossover: a remote prefix that would
    not save ``kv_import_crossover_tokens`` of prefill over the best
    local tier is not worth its pull cost — counted as a skip, and no
    fetch is issued at all."""
    p1 = BASE + [40]
    p2 = BASE + [51, 52, 53] + list(range(200, 212))
    directory = KvDirectory()
    a = _economy(model, "a", directory, prefill_len=64)
    calls = []
    b = _economy(model, "b", directory, kv_import_crossover_tokens=30,
                 fetch=lambda *args: calls.append(args), prefill_len=64)
    try:
        a.generate(p1, 6, timeout=120)  # advertises depth ~21 < want 30
        b.generate(p2, 6, timeout=120)
        mb = b.metrics()
        assert mb["kv_import_skipped_crossover"] == 1
        assert mb["kv_peer_hits"] == 0 and calls == []
    finally:
        a.stop()
        b.stop()


def test_export_prefix_misses_raise_keyerror(model):
    directory = KvDirectory()
    a = _economy(model, "a", directory)
    try:
        with pytest.raises(KeyError):
            a.export_prefix([1, 2, 3, 4, 5, 6, 7, 8])
    finally:
        a.stop()


def test_economy_requires_paged_layout(model):
    spec, params = model
    with pytest.raises(ValueError, match="paged"):
        ContinuousDecoder(params, spec.config, slots=2, prefill_len=32,
                          max_new_tokens=8,
                          kv_directory=KvDirectory(), replica_name="a")


def test_economy_metrics_surface(model):
    directory = KvDirectory()
    cold = ColdKvStore(1 << 20)
    a = _economy(model, "a", directory, cold=cold)
    try:
        a.generate(BASE + [40], 4, timeout=120)
        m = a.metrics()
        for k in ("kv_peer_hits", "kv_peer_misses", "kv_peer_import_bytes",
                  "kv_peer_fetch_failures", "kv_cold_hits",
                  "kv_cold_demotions", "kv_cold_import_bytes",
                  "kv_import_stale_refused", "kv_import_skipped_crossover",
                  "kv_directory_publishes", "kv_host_tier_high_water_bytes",
                  "kv_cold_store_bytes", "kv_directory_keys"):
            assert k in m, k
        assert m["kv_directory_publishes"] >= 1
    finally:
        a.stop()
