"""Fleet-serving tests: rendezvous hashing, prefix-affine placement,
pressure spill, and replica death (the chaos replica-kill scenario).

The unit half runs on stub replicas (pure host logic); the chaos half
drives REAL paged decoders through the DecoderFleet and kills one
mid-stream — streams on the dead replica must fail fast with the
502-equivalent error, its keys must remap to survivors (and ONLY its
keys), and the survivors must end the episode with zero leaked KV
blocks.
"""

from __future__ import annotations

import time

import pytest

from kubeflow_tpu.serving.affinity import (
    prefix_affinity_key,
    rendezvous_order,
    rendezvous_pick,
)
from kubeflow_tpu.serving.fleet import (
    DecoderFleet,
    ReplicaUnavailableError,
)


# ---------------------------------------------------------------------------
# Rendezvous hashing
# ---------------------------------------------------------------------------


def test_affinity_key_depends_only_on_leading_tokens():
    a = prefix_affinity_key([1, 2, 3, 4, 5, 6], width=4)
    b = prefix_affinity_key([1, 2, 3, 4, 99, 98], width=4)
    c = prefix_affinity_key([1, 2, 3, 5, 5, 6], width=4)
    assert a == b          # same leading 4 tokens → same key
    assert a != c          # divergence inside the window → new key
    assert prefix_affinity_key([1, 2], width=4) == \
        prefix_affinity_key([1, 2], width=4)


def test_rendezvous_order_is_stable_and_total():
    members = [f"r{i}" for i in range(5)]
    order = rendezvous_order("key-1", members)
    assert sorted(order) == sorted(members)
    assert order == rendezvous_order("key-1", list(reversed(members)))
    assert rendezvous_pick("key-1", members) == order[0]


def test_rendezvous_membership_churn_moves_about_one_nth():
    """Scale-up moves ~1/N of keys; scale-down moves ONLY the removed
    member's keys — the property that keeps every surviving replica's
    prefix trie warm across a scale event."""
    keys = [prefix_affinity_key([i, i + 1, i * 3]) for i in range(800)]
    four = [f"r{i}" for i in range(4)]
    five = four + ["r4"]
    a4 = {k: rendezvous_pick(k, four) for k in keys}
    a5 = {k: rendezvous_pick(k, five) for k in keys}
    moved = [k for k in keys if a4[k] != a5[k]]
    # Every moved key must have moved TO the new member (not reshuffled
    # among the old ones), and the moved fraction is ~1/5.
    assert all(a5[k] == "r4" for k in moved)
    assert 0.10 < len(moved) / len(keys) < 0.33
    # Scale-down (drop r2): only r2's keys move; everyone else stays.
    three = [m for m in four if m != "r2"]
    a3 = {k: rendezvous_pick(k, three) for k in keys}
    for k in keys:
        if a4[k] != "r2":
            assert a3[k] == a4[k]
        else:
            assert a3[k] != "r2"


def test_rendezvous_failover_order_is_exclusion_stable():
    """order[1] under full membership IS the pick once order[0] is
    excluded — the spill/failover sequence never reshuffles."""
    members = [f"r{i}" for i in range(6)]
    for key in ("a", "b", "c", "d"):
        order = rendezvous_order(key, members)
        rest = [m for m in members if m != order[0]]
        assert rendezvous_order(key, rest) == order[1:]


# ---------------------------------------------------------------------------
# DecoderFleet placement on stub replicas
# ---------------------------------------------------------------------------


class _StubReplica:
    """submit/metrics/stop-shaped stub with a settable queue depth."""

    def __init__(self, depth: int = 0):
        self._active_count = depth
        self._pending: list = []
        self.submitted: list = []
        self.dead = False

    def submit(self, tokens, want, temperature=0.0, *, request_id=None):
        if self.dead:
            raise RuntimeError("decoder is stopped")
        self.submitted.append(list(tokens))
        return object()

    def metrics(self):
        return {"prefix_hits": 0, "prefix_misses": len(self.submitted)}

    def stop(self):
        pass


def test_affine_routing_is_deterministic_and_affine():
    fleet = DecoderFleet({f"r{i}": _StubReplica() for i in range(4)},
                         affinity_tokens=8)
    toks = [5, 6, 7, 8, 9]
    picks = {fleet.route(toks) for _ in range(10)}
    assert len(picks) == 1  # same prompt, same replica, always
    key = prefix_affinity_key(toks, 8)
    assert picks.pop() == rendezvous_pick(key, fleet.members())


def test_spill_under_pressure_is_deterministic_least_loaded():
    reps = {f"r{i}": _StubReplica() for i in range(4)}
    fleet = DecoderFleet(reps, affinity_tokens=8, pressure=3)
    toks = [1, 2, 3]
    primary = fleet.route(toks)
    assert fleet.spilled == 0
    # Load the affine replica past the bound: the pick spills to the
    # least-loaded live replica, deterministically.
    reps[primary]._active_count = 3
    order = rendezvous_order(prefix_affinity_key(toks, 8),
                             fleet.members())
    reps[order[1]]._active_count = 2  # next-in-order is NOT least loaded
    spill = fleet.route(toks)
    assert spill != primary
    assert spill == min(order[1:],
                        key=lambda m: (reps[m]._active_count,
                                       order.index(m)))
    assert fleet.route(toks) == spill  # stable while load is stable
    assert fleet.spilled >= 2
    # Pressure relieved → the key returns home (no sticky spill).
    reps[primary]._active_count = 0
    assert fleet.route(toks) == primary


def test_affinity_concentrates_groups_vs_random_routing():
    """Prefix-affine placement sends a whole shared-prefix group to ONE
    replica; seeded-random routing spreads it — the trie-concentration
    property, pinned here on the placement alone and on real decoders by
    test_routing_moves_prefix_hits_never_tokens."""
    groups = {g: [[g, g + 1, g + 2, 7] + [r] for r in range(8)]
              for g in range(20)}
    affine = DecoderFleet({f"r{i}": _StubReplica() for i in range(4)},
                          affinity_tokens=4)
    rand = DecoderFleet({f"r{i}": _StubReplica() for i in range(4)},
                        affinity_tokens=4, router="random", seed=3)
    spread = {"affine": [], "random": []}
    for g, prompts in groups.items():
        spread["affine"].append(len({affine.route(p) for p in prompts}))
        spread["random"].append(len({rand.route(p) for p in prompts}))
    assert all(n == 1 for n in spread["affine"])
    assert sum(spread["random"]) / len(spread["random"]) > 2.0


def test_submit_remaps_off_dead_replica():
    reps = {f"r{i}": _StubReplica() for i in range(3)}
    fleet = DecoderFleet(reps, affinity_tokens=4)
    toks = [9, 8, 7]
    home = fleet.route(toks)
    reps[home].dead = True
    handle = fleet.submit(toks, 4)
    assert handle.replica != home
    assert home not in fleet.live_members()
    assert fleet.remapped == 1
    # Keys whose affine replica survived keep their placement.
    order = rendezvous_order(prefix_affinity_key(toks, 4),
                             ["r0", "r1", "r2"])
    assert handle.replica == [m for m in order if m != home][0]


def test_all_dead_raises_replica_unavailable():
    reps = {"r0": _StubReplica(), "r1": _StubReplica()}
    for r in reps.values():
        r.dead = True
    fleet = DecoderFleet(reps)
    with pytest.raises(ReplicaUnavailableError) as e:
        fleet.submit([1, 2], 4)
    assert e.value.code == 502


def test_gateway_route_parses_prefix_affine_spec():
    from kubeflow_tpu.gateway.routing import routes_from_service
    from kubeflow_tpu.manifests.core import (
        GATEWAY_ROUTE_ANNOTATION,
        gateway_route,
    )

    ann = gateway_route(
        "pool", "/models/m/", "m-r0.ns:8500",
        backends=[{"service": "m-r0.ns:8500", "weight": 1},
                  {"service": "m-r1.ns:8500", "weight": 1}],
        strategy="prefix-affine", affinity_tokens=24, pressure=6)
    svc = {"metadata": {"name": "m", "annotations": ann}}
    (route,) = routes_from_service(svc)
    assert route.strategy == "prefix-affine"
    assert route.affinity_tokens == 24
    assert route.pressure == 6
    # prefix-affine without a backends pool is a misconfiguration:
    # the route is rejected, not silently direct-routed.
    bad = gateway_route("solo", "/m/", "m.ns:8500",
                        strategy="prefix-affine")
    assert routes_from_service(
        {"metadata": {"name": "m", "annotations": {
            GATEWAY_ROUTE_ANNOTATION: bad[GATEWAY_ROUTE_ANNOTATION]
        }}}) == []


# ---------------------------------------------------------------------------
# Chaos: replica death mid-stream against real decoders
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    import jax

    from kubeflow_tpu.models.registry import get_model

    spec = get_model("lm-test-tiny")
    return spec, spec.init(jax.random.PRNGKey(0), spec.config)


def _decoder(tiny, **kw):
    from kubeflow_tpu.serving.continuous import ContinuousDecoder

    spec, params = tiny
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_len", 16)
    kw.setdefault("max_new_tokens", 192)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("stream_timeout_s", 60.0)
    return ContinuousDecoder(params, spec.config, **kw)


def test_routing_moves_prefix_hits_never_tokens(tiny):
    """Six groups of four prompts, each group sharing a 12-token prefix,
    served one at a time by three real replicas: whichever replica the
    router picks, tokens equal a lone cache-less decoder's; prefix-affine
    placement keeps a group on one trie and hits strictly more often than
    seeded-random placement; no slot still holds a block afterwards."""
    prompts = [[(g * 7 + j) % 97 + 3 for j in range(12)] + [200 + g, 11 + r]
               for g in range(6) for r in range(4)]
    kw = dict(max_new_tokens=8)
    lone = _decoder(tiny, **kw)
    try:
        want = [lone.generate(p, 4, timeout=60)["tokens"] for p in prompts]
    finally:
        lone.stop()

    def hits(router):
        reps = {f"r{i}": _decoder(tiny, prefix_cache_slots=8,
                                  prefix_cache_min_len=8, **kw)
                for i in range(3)}
        fleet = DecoderFleet(reps, affinity_tokens=12, router=router, seed=7)
        try:
            got = [fleet.generate(p, 4, timeout=60)["tokens"]
                   for p in prompts]
            assert got == want, router
            for nm, d in reps.items():
                assert all(not b for b in d._slot_blocks), (router, nm)
            return sum(d.metrics()["prefix_hits"] for d in reps.values())
        finally:
            fleet.stop()

    affine = hits("affine")
    assert affine == 6 * 3  # every follower of every group
    assert affine > hits("random")


def test_replica_kill_mid_stream_fails_fast_and_remaps(tiny):
    """The chaos scenario: one replica's scheduler loop dies while
    streams are in flight on it. Those streams fail FAST with the
    502-coded error (no hung clients waiting out the 60s timeout), the
    fleet excludes the replica, the dead replica's keys remap to
    survivors while survivors' keys stay put, and the survivors leak
    zero KV blocks."""
    reps = {f"r{i}": _decoder(tiny) for i in range(3)}
    fleet = DecoderFleet(reps, affinity_tokens=8)
    try:
        # Find prompts whose affine home covers every replica.
        home_of = {}
        probe = 0
        while set(home_of) != set(reps) and probe < 200:
            toks = [3 + probe % 11, 5, 7, probe % 13 + 2]
            home_of.setdefault(fleet.route(toks), toks)
            probe += 1
        assert set(home_of) == set(reps)
        victim = "r1"
        survivors = [nm for nm in reps if nm != victim]

        # Long generations in flight on every replica.
        handles = {nm: fleet.submit(toks, 192) for nm, toks in
                   home_of.items()}
        for nm, h in handles.items():
            assert h.replica == nm
        # Let decode get going, then kill the victim's scheduler the
        # ungraceful way: with the state lock held (the scheduler
        # parks at its next dispatch), poison the device state so that
        # dispatch raises and the loop's crash path (_fail_all) runs —
        # deterministically MID-stream, however fast the tiny model
        # decodes.
        stream = handles[victim].tokens(timeout=60)
        next(stream)  # stream is live
        with reps[victim]._state_lock:
            reps[victim]._state = None

        t0 = time.perf_counter()
        with pytest.raises(ReplicaUnavailableError) as err:
            for _ in stream:
                pass
        elapsed = time.perf_counter() - t0
        assert err.value.code == 502
        assert elapsed < 10, f"dead-replica stream hung {elapsed:.1f}s"
        assert victim not in fleet.live_members()

        # Survivors' streams complete untouched.
        for nm in survivors:
            res = handles[nm].result(timeout=60)
            assert len(res["tokens"]) == 192

        # The victim's keys remap to the NEXT replica in their own
        # rendezvous order; survivors' keys keep their home.
        h2 = fleet.submit(home_of[victim], 4)
        key = prefix_affinity_key(home_of[victim], 8)
        order = rendezvous_order(key, ["r0", "r1", "r2"])
        assert h2.replica == [m for m in order if m != victim][0]
        assert len(h2.result(timeout=60)["tokens"]) == 4
        for nm in survivors:
            h = fleet.submit(home_of[nm], 4)
            assert h.replica == nm
            h.result(timeout=60)  # drained before the leak check
        # Drained: zero blocks still held by any survivor slot.
        m = fleet.metrics()
        assert m["kv_blocks_in_use"] == 0
        for nm in survivors:
            assert all(not b for b in reps[nm]._slot_blocks)
        assert m["dead"] == [victim]
    finally:
        fleet.stop()


def test_prefill_replica_kill_mid_handoff(tiny):
    """Disaggregated chaos: a prefill replica's scheduler dies while
    decode streams are in flight. The handoff routed at it 502s
    fail-fast, the decode pool's streams are untouched, ONLY the dead
    replica's affinity keys remap inside the prefill pool, and neither
    pool leaks a block."""
    reps = {"p0": _decoder(tiny, role="prefill",
                           prefix_cache_slots=8, prefix_cache_min_len=8),
            "p1": _decoder(tiny, role="prefill",
                           prefix_cache_slots=8, prefix_cache_min_len=8),
            "d0": _decoder(tiny, role="decode",
                           prefix_cache_slots=8, prefix_cache_min_len=8),
            "d1": _decoder(tiny, role="decode",
                           prefix_cache_slots=8, prefix_cache_min_len=8)}
    fleet = DecoderFleet(reps, affinity_tokens=8)
    try:
        # Prompts whose affine PREFILL home covers both prefill
        # replicas (>= 10 tokens so the handoff prefix clears min_len).
        home_of = {}
        probe = 0
        while set(home_of) != {"p0", "p1"} and probe < 200:
            toks = [3 + probe % 11, 5, 7, probe % 13 + 2] + \
                [11 + probe % 3] * 8
            home_of.setdefault(fleet.route_prefill(toks), toks)
            probe += 1
        assert set(home_of) == {"p0", "p1"}
        victim, survivor = "p0", "p1"

        # Long decode streams in flight on the decode pool (submitted
        # through the two-hop while every prefill replica is healthy).
        streams = [fleet.submit(home_of[survivor][:-1] + [50 + i], 64)
                   for i in range(2)]
        assert {h.replica for h in streams} <= {"d0", "d1"}

        # Kill the victim's scheduler mid-life: poison the device state
        # under the state lock so its next dispatch raises.
        with reps[victim]._state_lock:
            reps[victim]._state = None

        # A submit whose affine prefill home is the victim: the
        # in-flight handoff fails FAST with the 502-coded error.
        t0 = time.perf_counter()
        with pytest.raises(ReplicaUnavailableError) as err:
            fleet.submit(home_of[victim], 4)
        elapsed = time.perf_counter() - t0
        assert err.value.code == 502
        assert elapsed < 10, f"dead-prefill handoff hung {elapsed:.1f}s"
        assert victim not in fleet.live_members()

        # Decode-pool streams are unaffected by the prefill death.
        for h in streams:
            assert len(h.result(timeout=120)["tokens"]) == 64

        # The victim's keys remap to the surviving prefill replica;
        # the survivor's keys never move. New submits succeed (handoff
        # rides the survivor).
        assert fleet.route_prefill(home_of[victim]) == survivor
        assert fleet.route_prefill(home_of[survivor]) == survivor
        out = fleet.submit(home_of[victim], 4)
        assert len(out.result(timeout=120)["tokens"]) == 4
        m = fleet.metrics()
        assert m["prefill_pool"] == [survivor]
        assert sorted(m["decode_pool"]) == ["d0", "d1"]
        assert m["dead"] == [victim]

        # Zero leaked blocks on BOTH pools: no slot holds blocks after
        # drain (the victim's _fail_all freed its reservations too),
        # and every surviving replica's residual refs are cache-held.
        for name, rep in reps.items():
            assert all(not blks for blks in rep._slot_blocks), name
        for name in ("p1", "d0", "d1"):
            rep = reps[name]
            with rep._prefix_lock:
                while rep.prefix_cache.evict_lru():
                    pass
            assert rep._alloc.blocks_in_use == 0, name
    finally:
        fleet.stop()


def test_fleet_metrics_aggregate_live_replicas(tiny):
    reps = {"a": _decoder(tiny), "b": _decoder(tiny)}
    fleet = DecoderFleet(reps, affinity_tokens=4)
    try:
        fleet.generate([1, 2, 3], 4, timeout=60)
        m = fleet.metrics()
        assert m["tokens_emitted"] == 4
        assert sorted(m["replicas"]) == ["a", "b"]
        assert m["live"] == ["a", "b"]
        assert m["routed"] == 1
    finally:
        fleet.stop()


def test_metrics_snapshot_consistent_under_concurrent_mark_dead():
    """PR-11 regression (tpu-lint lock-inconsistent-guard): metrics()
    iterated the mutable dead set and read the routing counters without
    the fleet lock while mark_dead() ran on caller threads — a torn
    read at best, a set-changed-size RuntimeError at worst. It now
    snapshots under the lock: live/dead always partition the
    membership."""
    import threading

    reps = {f"r{i:02d}": _StubReplica() for i in range(24)}
    fleet = DecoderFleet(reps, affinity_tokens=4)
    errors: list[Exception] = []
    stop = threading.Event()

    def hammer():
        try:
            while not stop.is_set():
                m = fleet.metrics()
                live, dead = set(m["live"]), set(m["dead"])
                assert live | dead == set(reps)
                assert not live & dead
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        for name in sorted(reps)[:-1]:  # keep one live member
            fleet.mark_dead(name)
            time.sleep(0.002)
    finally:
        stop.set()
        t.join(timeout=10)
    assert not errors, errors
