"""Live weight streaming tests: the zero-drain swap
(ContinuousDecoder.update_weights), weight-version-stamped prefix/tier
KV (cold-vs-warm identical after a swap, stale entries never served),
the draft-model pairing, the chunked push envelope + HTTP endpoint,
and the fleet broadcast with mid-push death and bounded version skew.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.serving import weights as weights_mod
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.fleet import DecoderFleet

SPEC = get_model("lm-test-tiny")
P1 = SPEC.init(jax.random.PRNGKey(0), SPEC.config)
P2 = SPEC.init(jax.random.PRNGKey(1), SPEC.config)

PREFILL, GEN = 32, 12
PROMPT = [3 + (j % 23) for j in range(12)]


def mk(params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_len", PREFILL)
    kw.setdefault("max_new_tokens", GEN)
    kw.setdefault("prefix_cache_slots", 4)
    kw.setdefault("prefix_cache_min_len", 6)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 4)
    kw.setdefault("stream_timeout_s", 120.0)
    return ContinuousDecoder(params, SPEC.config, **kw)


def gen_tokens(d, prompt=PROMPT, want=GEN):
    return d.generate(list(prompt), want, timeout=120)["tokens"]


def cold_tokens(params, prompt=PROMPT, want=GEN, **kw):
    d = mk(params, **kw)
    try:
        return gen_tokens(d, prompt, want)
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# The zero-drain swap
# ---------------------------------------------------------------------------


def test_swap_byte_identity_and_version():
    d = mk(P1)
    try:
        pre = gen_tokens(d)
        assert d.metrics()["weights_version"] == 0
        v = d.update_weights(P2)
        assert v == 1
        m = d.metrics()
        assert m["weights_version"] == 1
        assert m["weight_pushes"] == 1
        assert m["weight_swap_seconds_last"] >= 0
        post = gen_tokens(d)
    finally:
        d.stop()
    assert pre == cold_tokens(P1)
    # Acceptance gate: post-swap greedy tokens byte-identical to a
    # decoder cold-started on the pushed weights — the prompt's v0 trie
    # entry must have been flushed/refused, never served.
    assert post == cold_tokens(P2)
    assert pre != post  # differently-seeded weights actually differ


def test_swap_byte_identity_int8_and_tp():
    legs = [{"kv_dtype": "int8"}]
    if jax.device_count() >= 2:
        legs.append({"tp_shards": 2})
    for kw in legs:
        d = mk(P1, **kw)
        try:
            gen_tokens(d)          # publish under v0
            d.update_weights(P2)
            post = gen_tokens(d)
        finally:
            d.stop()
        assert post == cold_tokens(P2, **kw), kw


def test_stale_version_push_is_noop():
    d = mk(P1)
    try:
        assert d.update_weights(P2, version=5) == 5
        # Duplicate and stale pushes: no-op returning the installed
        # epoch (fleet stragglers re-deliver without harm).
        assert d.update_weights(P1, version=5) == 5
        assert d.update_weights(P1, version=3) == 5
        assert d.metrics()["weight_pushes"] == 1
        assert gen_tokens(d) == cold_tokens(P2)
    finally:
        d.stop()


def test_update_weights_validation():
    d = mk(P1)
    try:
        bad = jax.tree.map(lambda a: np.zeros((2, 2), np.float32), P1)
        with pytest.raises(ValueError):
            d.update_weights(bad)
        with pytest.raises(ValueError):
            d.update_weights({"not": "a matching tree"})
        # A failed push must leave the serving weights untouched.
        assert d.metrics()["weights_version"] == 0
        assert gen_tokens(d) == cold_tokens(P1)
    finally:
        d.stop()


def test_stale_prefix_refused_and_counted():
    d = mk(P1)
    try:
        gen_tokens(d)  # publishes PROMPT's prefix under epoch 0
        assert d.metrics()["prefix_entries"] >= 1
        d.update_weights(P2)
        # The flush already removed the unpinned stale entry, so the
        # next admission is a clean miss (not a stale serve).
        m0 = d.metrics()
        post = gen_tokens(d)
        m1 = d.metrics()
        assert post == cold_tokens(P2)
        # Either path is correct — swept at swap, or refused at match —
        # but a stale entry must never SERVE.
        assert (m0["prefix_entries"] == 0
                or m1["weights_stale_refused"] >= 1)
        assert m1["prefix_hits"] == m0["prefix_hits"]
    finally:
        d.stop()


def test_pinned_stale_entry_refused_at_match():
    """An entry pinned by an in-flight stream survives the swap's
    flush; the next fresh match must refuse (and then remove) it."""
    d = mk(P1)
    try:
        gen_tokens(d)  # publish under epoch 0
        with d._prefix_lock:
            entry = d.prefix_cache.entries()[0]
            entry.refs += 1  # simulate an in-flight reader's pin
        d.update_weights(P2)
        assert d.metrics()["prefix_entries"] == 1  # pinned: survived
        with d._prefix_lock:
            entry.refs -= 1
        post = gen_tokens(d)
        m = d.metrics()
        assert post == cold_tokens(P2)
        assert m["weights_stale_refused"] >= 1
        assert all(e.version == 1
                   for e in d.prefix_cache.entries())
    finally:
        d.stop()


def test_host_tier_stale_never_promoted():
    d = mk(P1, host_kv_bytes=32 << 20)
    try:
        gen_tokens(d)
        # Demote the published prefix to the host tier (epoch 0).
        with d._prefix_lock:
            while d.prefix_cache.evict_lru():
                pass
        assert d.metrics()["kv_host_tier_entries"] >= 1
        d.update_weights(P2)
        post = gen_tokens(d)
        m = d.metrics()
        assert post == cold_tokens(P2)
        assert m["kv_host_hits"] == 0  # stale payload never promoted
    finally:
        d.stop()


def test_streams_straddle_swap_without_disruption():
    """Identical-weights push mid-decode: the boundary must be
    invisible — every straddling stream byte-identical to an
    undisturbed run, none dropped or errored."""
    d = mk(P1, slots=4, max_new_tokens=24)
    results: dict[int, list] = {}

    def prompt(i):
        return PROMPT + [7 + i] * 3

    def one(i):
        out = []
        for tok in d.submit(prompt(i), 24).tokens(timeout=120):
            out.append(tok)
        results[i] = out

    try:
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(3)]
        for th in threads:
            th.start()
        deadline = time.perf_counter() + 10
        while (d.metrics()["in_flight"] < 1
               and time.perf_counter() < deadline):
            time.sleep(0.002)
        d.update_weights(P1)  # same weights, new epoch
        for th in threads:
            th.join(timeout=120)
        # The swap leaked nothing: no slot still holds a block.
        assert all(not blks for blks in d._slot_blocks)
    finally:
        d.stop()
    assert sorted(results) == [0, 1, 2]
    for i in range(3):
        assert results[i] == cold_tokens(P1, prompt(i), 24,
                                        max_new_tokens=24), i


def test_straddling_stream_single_boundary_and_no_publish():
    """A stream straddling a REAL weight change: completes its full
    budget, its output agrees with the old-weights run up to a single
    divergence point, and its prompt KV never enters the trie."""
    d = mk(P1, slots=2, max_new_tokens=24, chunk_size=1)
    ref = cold_tokens(P1, PROMPT, 24, max_new_tokens=24)
    out: list[int] = []
    try:
        h = d.submit(list(PROMPT), 24)
        it = h.tokens(timeout=120)
        for _ in range(4):  # let a few v0 tokens land
            out.append(next(it))
        d.update_weights(P2)
        for tok in it:
            out.append(tok)
    finally:
        d.stop()
    assert len(out) == 24
    assert out[:4] == ref[:4]
    # Single version boundary: once diverged from the old-weights
    # trajectory, the stream is on the new weights — it must not
    # interleave back and forth. (With KV kept, the new-weights
    # continuation is mixed-KV; we pin the prefix property.)
    i = 0
    while i < 24 and out[i] == ref[i]:
        i += 1
    assert i >= 4
    # The straddler must not have published its (old-epoch) prompt KV.
    assert all(e.version == 1 for e in d.prefix_cache.entries())


# ---------------------------------------------------------------------------
# Draft-model pairing
# ---------------------------------------------------------------------------


def test_draft_pairing_keeps_acceptance_above_floor():
    d = mk(P1, slots=2, speculative_k=4,
           draft_mode="model:lm-test-tiny", max_new_tokens=24)
    try:
        # Pair draft and target on the SAME weights in one epoch: the
        # draft's greedy proposals then equal the target's greedy
        # choices, so acceptance must sit near 1.0. An unpaired swap
        # would leave the draft on its own random init — the silent
        # acceptance collapse the pairing exists to prevent.
        v = d.update_weights(P2, draft_params=P2)
        assert v == 1
        toks = gen_tokens(d, PROMPT, 24)
        m = d.metrics()
        assert toks == cold_tokens(P2, PROMPT, 24, max_new_tokens=24)
        assert m["spec_drafted_tokens"] > 0
        assert m["spec_acceptance_rate"] > 0.8, m["spec_acceptance_rate"]
    finally:
        d.stop()


def test_draft_params_without_proposer_rejected():
    d = mk(P1)
    try:
        with pytest.raises(ValueError):
            d.update_weights(P2, draft_params=P2)
        assert d.metrics()["weights_version"] == 0
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# Chunked envelope + assembler
# ---------------------------------------------------------------------------


def test_envelope_roundtrip_and_chunking():
    chunks = weights_mod.pack_weights(P1, 3, chunk_bytes=1024)
    assert len(chunks) > 1  # tiny bound forces a real split
    assert all(c["chunks"] == len(chunks) for c in chunks)
    asm = weights_mod.WeightChunkAssembler()
    # Deliver out of order with a duplicate: idempotent, installs once.
    order = list(reversed(chunks))
    done = None
    for env in [order[0]] + order:
        decoded = weights_mod.unpack_chunk(json.loads(json.dumps(env)))
        res = asm.add(decoded)
        if res is not None:
            assert done is None
            done = res
    assert done is not None
    leaves, has_draft = done
    assert not has_draft
    model_leaves, draft_leaves = weights_mod.split_namespaces(leaves)
    assert not draft_leaves
    rebuilt = weights_mod.unflatten_params(model_leaves, P1)
    ref_flat = jax.tree_util.tree_leaves(P1)
    for a, b in zip(jax.tree_util.tree_leaves(rebuilt), ref_flat):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_assembler_epoch_handling():
    asm = weights_mod.WeightChunkAssembler()
    old = weights_mod.pack_weights(P1, 1, chunk_bytes=1024)
    new = weights_mod.pack_weights(P2, 2, chunk_bytes=1024)
    assert asm.add(weights_mod.unpack_chunk(old[0])) is None
    # A newer epoch's chunk discards the stale partial push.
    for env in new:
        res = asm.add(weights_mod.unpack_chunk(env))
    assert res is not None
    # A chunk for an older epoch than one being assembled is refused.
    asm.add(weights_mod.unpack_chunk(
        weights_mod.pack_weights(P2, 5, chunk_bytes=1024)[0]))
    with pytest.raises(ValueError):
        asm.add(weights_mod.unpack_chunk(old[0]))


def test_unflatten_refuses_partial_or_extra():
    leaves = weights_mod.flatten_params(P1)
    partial = dict(list(leaves.items())[:-1])
    with pytest.raises(ValueError):
        weights_mod.unflatten_params(partial, P1)
    extra = dict(leaves)
    extra["bogus/leaf"] = np.zeros((1,), np.float32)
    with pytest.raises(ValueError):
        weights_mod.unflatten_params(extra, P1)


def test_unpack_chunk_rejects_garbage():
    with pytest.raises(ValueError):
        weights_mod.unpack_chunk({"version": 99})
    with pytest.raises(ValueError):
        weights_mod.unpack_chunk(
            {"version": 1, "weights_version": 1, "seq": 2, "chunks": 2,
             "leaves": {}})
    with pytest.raises(ValueError):
        weights_mod.unpack_chunk(
            {"version": 1, "weights_version": 1, "seq": 0, "chunks": 1,
             "leaves": "nope"})


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------


def test_http_weights_endpoint_chunked_push():
    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.server import ModelServer

    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=4, max_seq_len=32,
                     max_new_tokens=GEN, kv_layout="paged",
                     kv_block_size=4),
        port=0, grpc_port=None, batch_timeout_ms=2)
    server.start()
    try:
        decoder = server.decoder
        assert decoder is not None
        pre = gen_tokens(decoder)
        assert pre == cold_tokens(P1)  # server inits from seed 0
        out = weights_mod.push_weights(
            f"127.0.0.1:{server.port}", "lm-test-tiny", P2, 1,
            chunk_bytes=1024)
        assert out == {"installed": True, "weights_version": 1}
        assert decoder.metrics()["weights_version"] == 1
        assert gen_tokens(decoder) == cold_tokens(P2)
        # Stale re-push: accepted transport-wise, installs nothing new.
        out = weights_mod.push_weights(
            f"127.0.0.1:{server.port}", "lm-test-tiny", P1, 1,
            chunk_bytes=1024)
        assert out["weights_version"] == 1
        assert decoder.metrics()["weight_pushes"] == 1
        # Garbage envelope → 400, not an install.
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}"
            "/v1/models/lm-test-tiny:weights",
            data=json.dumps({"version": 42}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Fleet broadcast
# ---------------------------------------------------------------------------


def test_broadcast_converges_fleet():
    fleet = DecoderFleet({f"r{i}": mk(P1) for i in range(3)})
    try:
        res = fleet.broadcast_weights(P2)
        assert res["version"] == 1
        assert sorted(res["installed"]) == ["r0", "r1", "r2"]
        assert not res["failed"] and not res["lagging"]
        vv = fleet.weights_versions()
        assert vv["latest"] == 1
        assert set(vv["installed"].values()) == {1}
        # Every replica serves the new weights.
        want = cold_tokens(P2)
        for name in fleet.members():
            assert gen_tokens(fleet._replicas[name]) == want
    finally:
        fleet.stop()


class _StubReplica:
    """Duck-typed replica for routing/broadcast bookkeeping tests.
    ``fail`` raises a death-class error (replica gone); ``refuse``
    raises a push-fault (ValueError — replica healthy, push bad),
    which produces LAG without death."""

    def __init__(self, fail=False, refuse=False):
        self.fail = fail
        self.refuse = refuse
        self.version = 0
        self.submits = 0
        self.role = ""

    def update_weights(self, params, *, version=None, draft_params=None):
        if self.fail:
            raise RuntimeError("replica died mid-push")
        if self.refuse:
            raise ValueError("pushed leaf shape mismatch")
        self.version = version
        return version

    def submit(self, tokens, want, temperature=0.0, *, request_id=None,
               **kw):
        self.submits += 1

        class _H:
            def result(self, timeout=None, **kw2):
                return {"tokens": [1], "finish_reason": "length"}

        return _H()

    def metrics(self):
        return {"in_flight": 0}

    def stop(self):
        pass


def test_broadcast_tolerates_mid_push_death_and_bounds_lag():
    a, b, c = _StubReplica(), _StubReplica(fail=True), _StubReplica()
    fleet = DecoderFleet({"a": a, "b": b, "c": c}, weights_max_lag=1)
    res = fleet.broadcast_weights(P1)
    # The dying replica is excluded; the broadcast completes on the
    # survivors.
    assert sorted(res["installed"]) == ["a", "c"]
    assert "b" in res["failed"]
    assert fleet.live_members() == ["a", "c"]
    # A second push: survivors advance to epoch 2; the dead replica
    # stays out of routing entirely.
    res2 = fleet.broadcast_weights(P1)
    assert res2["version"] == 2
    for _ in range(6):
        fleet.submit([1, 2, 3, 4], 1).result(timeout=5)
    assert b.submits == 0


def test_max_lag_excludes_stale_replica_from_routing():
    a, b = _StubReplica(), _StubReplica()
    fleet = DecoderFleet({"a": a, "b": b}, weights_max_lag=1,
                         affinity_tokens=4)
    fleet.broadcast_weights(P1)
    # b stops installing without dying (push-fault): pushes keep
    # landing on a only, so b LAGS while staying alive.
    b.refuse = True
    fleet.broadcast_weights(P1)
    fleet.broadcast_weights(P1)
    vv = fleet.weights_versions()
    assert vv["latest"] == 3 and vv["installed"]["b"] == 1
    assert fleet.live_members() == ["a", "b"]  # lagging, not dead
    b.submits = a.submits = 0
    for i in range(8):
        fleet.submit([i, i + 1, i + 2, 9], 1).result(timeout=5)
    # b lags by 2 > max_lag 1: every submit routes to a.
    assert b.submits == 0 and a.submits == 8
    # The straggler converges on the next successful push and rejoins.
    b.refuse = False
    fleet.broadcast_weights(P1)
    assert fleet.weights_versions()["installed"]["b"] == 4
    for i in range(16):
        fleet.submit([i, 5, 6, 7], 1).result(timeout=5)
    assert b.submits > 0
