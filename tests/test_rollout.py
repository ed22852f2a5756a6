"""RolloutController unit tests: the SLO-gated canary walk end to end
against a stub fleet — good candidate promotes through every step, a
latency-regressed candidate rolls back with evidence, stale scrapes
hold the walk, and the InferenceServiceController renders status.rollout
into the gateway's hash-split route. The hash-split Route mechanics
(stable assignment, shadow sampling, validation) are covered here too.
"""

from __future__ import annotations

import yaml

import pytest

from kubeflow_tpu.apis.inference import (
    inference_service,
    inference_service_crd,
)
from kubeflow_tpu.gateway.routing import (
    Route,
    routes_from_service,
    stable_hash01,
)
from kubeflow_tpu.manifests.core import GATEWAY_ROUTE_ANNOTATION
from kubeflow_tpu.operators.inference import InferenceServiceController
from kubeflow_tpu.operators.rollout import RolloutController

NS = "kubeflow"

CALM = {"queue_wait_p99_s": 0.05, "ttft_p99_s": 0.1,
        "inter_token_p99_s": 0.02, "kv_utilization": 0.2,
        "queued": 0.0, "error_rate": 0.0}
SLOW = {**CALM, "ttft_p99_s": 1.0}  # > 0.1 * gateRatio(1.5)
ERRORING = {**CALM, "error_rate": 0.5}


class StubFleet:
    """DecoderFleet's rollout-facing surface: named members with
    monotonic per-replica installed epochs (stale/duplicate pushes
    no-op, exactly like ContinuousDecoder.update_weights), targeted
    ``members=`` pushes, and a dead set whose pushes fail."""

    def __init__(self, members, epoch=1):
        self.installed = {m: epoch for m in members}
        self.latest = epoch
        self.dead: set[str] = set()
        self.pushes: list[tuple[int, tuple, object]] = []
        self.params_of: dict[str, object] = {m: "P1" for m in members}

    def members(self):
        return sorted(self.installed)

    def live_members(self):
        return sorted(set(self.installed) - self.dead)

    def weights_versions(self):
        return {"latest": self.latest,
                "installed": dict(self.installed), "max_lag": 1}

    def broadcast_weights(self, params, *, version=None,
                          draft_params=None, members=None):
        if version is not None:
            target = int(version)
        else:
            # Auto-increment CLAIMS the epoch (DecoderFleet semantics):
            # racing pushes pick distinct numbers.
            target = self.latest + 1
            self.latest = target
        names = self.members() if members is None else \
            [m for m in self.members() if m in set(members)]
        self.pushes.append((target, tuple(names), params))
        installed, failed = {}, {}
        for m in names:
            if m in self.dead:
                failed[m] = "replica dead"
                continue
            if target > self.installed[m]:
                self.installed[m] = target
                self.params_of[m] = params
            installed[m] = self.installed[m]
        if installed:
            self.latest = max(self.latest, max(installed.values()))
        return {"version": target, "installed": installed,
                "failed": failed, "lagging": []}


@pytest.fixture()
def renv(api):
    api.apply(inference_service_crd())
    clock = {"t": 0.0}
    fleet = StubFleet([f"llm-r{i}" for i in range(4)])
    sig = {"default": dict(CALM), "by_addr": {}}

    def fetch(addr):
        v = sig["by_addr"].get(addr, sig["default"])
        return dict(v) if v is not None else None

    weights = {"ckpt/v1": "W-INCUMBENT", "ckpt/v2": "W-CANDIDATE"}
    rc = RolloutController(api, fleet_for=lambda ns, n: fleet,
                           weights_for=weights.get,
                           fetch_metrics=fetch,
                           clock=lambda: clock["t"])
    ic = InferenceServiceController(api, fetch_metrics=fetch,
                                    clock=lambda: clock["t"])
    return api, rc, ic, fleet, clock, sig


def _cr(name="llm", **kw):
    kw.setdefault("replicas", 4)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("versions", [
        {"name": "v1", "weightsRef": "ckpt/v1", "traffic": 0},
        {"name": "v2", "weightsRef": "ckpt/v2", "traffic": 100}])
    kw.setdefault("rollout", {"stepSeconds": 1.0, "shadowSeconds": 1.0})
    kw.setdefault("autoscale", {"scrapePeriodSeconds": 5,
                                "signalStalenessSeconds": 20})
    return inference_service(name, NS, "lm-test-tiny", **kw)


def _rollout(api, name="llm"):
    return api.get("kubeflow-tpu.org/v1", "InferenceService", name,
                   NS).get("status", {}).get("rollout", {})


def _route(api, name="llm"):
    svc = api.get("v1", "Service", name, NS)
    return yaml.safe_load(
        svc["metadata"]["annotations"][GATEWAY_ROUTE_ANNOTATION])


def _drive(rc, clock, rounds, dt=2.0):
    for _ in range(rounds):
        clock["t"] += dt
        rc.reconcile_all()


# ---------------------------------------------------------------------------
# The walk
# ---------------------------------------------------------------------------


def test_good_candidate_walks_and_promotes(renv):
    api, rc, ic, fleet, clock, _sig = renv
    api.create(_cr())
    rc.reconcile_all()
    ro = _rollout(api)
    assert ro["phase"] == "Shadow"
    assert ro["candidate"]["epoch"] == 2
    assert ro["incumbent"]["epoch"] == 1
    # One canary replica (tail of the sorted members) already holds the
    # candidate epoch — the push happened, no new pods did.
    assert ro["canaryMembers"] == ["llm-r3"]
    assert fleet.installed["llm-r3"] == 2
    assert fleet.installed["llm-r0"] == 1

    # Walk: 1 -> 10 -> 50 -> 100, one gated step per dwell.
    _drive(rc, clock, 1)
    ro = _rollout(api)
    assert (ro["phase"], ro["trafficPercent"]) == ("Walking", 1.0)
    _drive(rc, clock, 2)
    ro = _rollout(api)
    assert ro["trafficPercent"] == 50.0
    assert len(ro["canaryMembers"]) == 2
    _drive(rc, clock, 2)
    ro = _rollout(api)
    assert ro["phase"] == "Promoted"
    assert ro["promotedEpoch"] == 2
    # The whole fleet converged on the candidate epoch and params.
    assert set(fleet.installed.values()) == {2}
    assert all(p == "W-CANDIDATE" for p in fleet.params_of.values())
    # Terminal: a further reconcile pushes nothing new.
    n_pushes = len(fleet.pushes)
    _drive(rc, clock, 1)
    assert len(fleet.pushes) == n_pushes


def test_regressed_candidate_rolls_back_with_evidence(renv):
    api, rc, ic, fleet, clock, sig = renv
    api.create(_cr())
    rc.reconcile_all()
    assert _rollout(api)["phase"] == "Shadow"
    # The canary cohort regresses: its TTFT p99 blows past
    # incumbent * gateRatio while the walk is live.
    sig["by_addr"][f"llm-r3.{NS}:8500"] = dict(SLOW)
    _drive(rc, clock, 1)
    ro = _rollout(api)
    assert ro["phase"] == "RolledBack"
    ev = ro["evidence"]
    assert ev["reason"] == "gate-breach"
    assert ev["signal"] == "ttftP99"
    assert ev["candidate"] == pytest.approx(1.0)
    assert ev["incumbent"] == pytest.approx(0.1)
    assert ev["gateRatio"] == 1.5
    # Rollback was a PUSH: incumbent params at a FRESH epoch (3 — the
    # canary already held 2; replaying epoch 1 would be a no-op), and
    # the fleet is uniform again.
    assert ro["rolledBackEpoch"] == 3
    assert set(fleet.installed.values()) == {3}
    assert all(p == "W-INCUMBENT" for p in fleet.params_of.values())
    # A rolled-back candidate must NOT auto-retry.
    _drive(rc, clock, 2)
    assert _rollout(api)["phase"] == "RolledBack"


@pytest.mark.parametrize("regress_canary, phase, reason", [
    (False, "Promoted", None), (True, "RolledBack", "gate-breach")],
    ids=["good-push", "bad-push"])
def test_walk_over_real_decoders_serves_the_winner(api, regress_canary,
                                                   phase, reason):
    """The same two walks over a DecoderFleet of ContinuousDecoders: the
    fleet ends on ONE epoch and its greedy tokens equal a decoder
    cold-started on the winner's weights (the candidate after a
    promotion; the incumbent, restored exactly, after a rollback)."""
    import jax

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.serving.continuous import ContinuousDecoder
    from kubeflow_tpu.serving.fleet import DecoderFleet

    spec = get_model("lm-test-tiny")
    p_inc = spec.init(jax.random.PRNGKey(0), spec.config)
    p_cand = spec.init(jax.random.PRNGKey(1), spec.config)
    prompts = [[3 + j % 29 for j in range(10)] + [5 + i] * 4
               for i in range(3)]

    def mk(params):
        return ContinuousDecoder(params, spec.config, slots=2,
                                 prefill_len=16, max_new_tokens=8)

    api.apply(inference_service_crd())
    api.create(_cr(replicas=3, max_replicas=3))
    fleet = DecoderFleet({f"llm-r{i}": mk(p_inc) for i in range(3)})
    clock = {"t": 0.0}

    def fetch(addr):
        canaries = {f"{m}.{NS}:8500"
                    for m in _rollout(api).get("canaryMembers", [])}
        return dict(SLOW if regress_canary and addr in canaries else CALM)

    rc = RolloutController(
        api, fleet_for=lambda ns, n: fleet,
        weights_for={"ckpt/v1": p_inc, "ckpt/v2": p_cand}.get,
        fetch_metrics=fetch, clock=lambda: clock["t"])
    try:
        rc.reconcile_all()
        _drive(rc, clock, 8)  # the walk and a terminal convergence pass
        ro = _rollout(api)
        assert ro["phase"] == phase
        assert (ro.get("evidence") or {}).get("reason") == reason
        installed = fleet.weights_versions()["installed"]
        assert len({installed[m] for m in fleet.live_members()}) == 1
        got = [fleet.generate(p, 8, timeout=120)["tokens"] for p in prompts]
    finally:
        fleet.stop()
    cold = mk(p_inc if regress_canary else p_cand)
    try:
        assert got == [cold.generate(p, 8, timeout=120)["tokens"]
                       for p in prompts]
    finally:
        cold.stop()


def test_error_rate_gate_breaches(renv):
    api, rc, ic, fleet, clock, sig = renv
    api.create(_cr())
    rc.reconcile_all()
    sig["by_addr"][f"llm-r3.{NS}:8500"] = dict(ERRORING)
    _drive(rc, clock, 1)
    ro = _rollout(api)
    assert ro["phase"] == "RolledBack"
    assert ro["evidence"]["signal"] == "errorRate"
    assert set(fleet.installed.values()) == {3}


def test_stale_scrape_holds_never_rolls_back(renv):
    """A transient scrape failure substitutes the last-good sample and
    HOLDS: no step advance, no rollback — the staleness satellite's
    contract applied to the rollout gate."""
    api, rc, ic, fleet, clock, sig = renv
    api.create(_cr())
    rc.reconcile_all()
    ro0 = _rollout(api)
    # Canary scrape starts failing (but its last-good sample is fresh
    # enough to hold).
    sig["by_addr"][f"llm-r3.{NS}:8500"] = None
    _drive(rc, clock, 3)
    ro = _rollout(api)
    assert ro["phase"] in ("Shadow", "Walking")
    assert ro["trafficPercent"] == ro0["trafficPercent"]
    assert ro.get("gate", {}).get("held") == "stale scrape signals"
    # Scrapes recover: the walk resumes where it held.
    sig["by_addr"].pop(f"llm-r3.{NS}:8500")
    _drive(rc, clock, 5)
    assert _rollout(api)["phase"] == "Promoted"


def test_quorum_loss_rolls_back(renv):
    """Canary replicas that stop being scrapeable past the staleness
    window are unobservable — losing quorum of them is a rollback (with
    evidence), not an indefinite hold."""
    api, rc, ic, fleet, clock, sig = renv
    api.create(_cr())
    rc.reconcile_all()
    sig["by_addr"][f"llm-r3.{NS}:8500"] = None
    # Past signalStalenessSeconds (20): held sample expires, the only
    # canary becomes unobservable, quorum (0.5) is gone.
    _drive(rc, clock, 1, dt=25.0)
    ro = _rollout(api)
    assert ro["phase"] == "RolledBack"
    assert ro["evidence"]["reason"] == "quorum-loss"
    assert ro["evidence"]["scrapedCanaries"] == 0
    assert set(fleet.installed.values()) == {3}


def test_single_version_spec_is_ignored(renv):
    api, rc, ic, fleet, clock, _sig = renv
    api.create(inference_service("plain", NS, "lm-test-tiny"))
    rc.reconcile_all()
    assert _rollout(api, "plain") == {}
    assert fleet.pushes == []


def test_missing_fleet_parks_in_pending(api):
    api.apply(inference_service_crd())
    rc = RolloutController(api, fleet_for=lambda ns, n: None,
                           weights_for=lambda ref: "W",
                           fetch_metrics=lambda a: dict(CALM),
                           clock=lambda: 0.0)
    api.create(_cr())
    rc.reconcile_all()
    ro = _rollout(api)
    assert ro["phase"] == "Pending"
    assert ro["reason"] == "no fleet handle"


# ---------------------------------------------------------------------------
# Router rendering (InferenceServiceController reads status.rollout)
# ---------------------------------------------------------------------------


def test_router_renders_hash_split_during_walk(renv):
    api, rc, ic, fleet, clock, _sig = renv
    api.create(_cr())
    ic.reconcile_all()  # replicas + plain route first
    assert _route(api)["strategy"] == "prefix-affine"
    rc.reconcile_all()  # Shadow
    ic.reconcile_all()
    route = _route(api)
    assert route["strategy"] == "hash-split"
    assert route["shadow"] == f"llm-r3.{NS}:8500"
    assert route["shadow_fraction"] == 0.1
    splits = {s["version"]: s for s in route["splits"]}
    assert splits["v2"]["weight"] == 0.0  # shadow: no user traffic yet
    assert splits["v2"]["backends"] == [f"llm-r3.{NS}:8500"]
    assert splits["v1"]["weight"] == 100.0
    assert len(splits["v1"]["backends"]) == 3

    _drive(rc, clock, 2)  # -> Walking at 10%
    ic.reconcile_all()
    route = _route(api)
    splits = {s["version"]: s for s in route["splits"]}
    assert splits["v2"]["weight"] == 10.0
    assert "shadow" not in route  # mirroring is a Shadow-phase tool

    _drive(rc, clock, 3)  # -> Promoted
    ic.reconcile_all()
    route = _route(api)
    assert route["strategy"] == "prefix-affine"
    assert "splits" not in route


def test_router_resets_after_rollback(renv):
    api, rc, ic, fleet, clock, sig = renv
    api.create(_cr())
    rc.reconcile_all()
    ic.reconcile_all()
    assert _route(api)["strategy"] == "hash-split"
    sig["by_addr"][f"llm-r3.{NS}:8500"] = dict(SLOW)
    _drive(rc, clock, 1)
    ic.reconcile_all()
    assert _route(api)["strategy"] == "prefix-affine"
    assert "splits" not in _route(api)


# ---------------------------------------------------------------------------
# hash-split Route mechanics
# ---------------------------------------------------------------------------


def _split_route(w_v1=90.0, w_v2=10.0, shadow_fraction=1.0):
    return Route(
        name="r", prefix="/models/m/", service="a:1",
        strategy="hash-split",
        backends=(("a:1", 1.0), ("b:1", 1.0), ("c:1", 1.0)),
        splits=(("v1", w_v1, ("a:1", "b:1")), ("v2", w_v2, ("c:1",))),
        shadow="c:1", shadow_fraction=shadow_fraction)


def test_pick_split_is_stable_and_weighted():
    r = _split_route()
    keys = [f"prefix-{i}".encode() for i in range(2000)]
    first = [r.pick_split(k)[0] for k in keys]
    # Deterministic: the same key maps to the same version forever.
    assert [r.pick_split(k)[0] for k in keys] == first
    share = first.count("v2") / len(first)
    assert 0.06 < share < 0.14  # ~10% ± sampling noise
    # Weight 0 -> no assignments at all (the Shadow-phase split).
    r0 = _split_route(100.0, 0.0)
    assert all(r0.pick_split(k)[0] == "v1" for k in keys)


def test_mirror_sample_fraction_and_determinism():
    r = _split_route(shadow_fraction=0.25)
    keys = [f"conv-{i}".encode() for i in range(2000)]
    sampled = [r.mirror_sample(k) for k in keys]
    assert sampled == [r.mirror_sample(k) for k in keys]
    share = sum(sampled) / len(sampled)
    assert 0.19 < share < 0.31
    # Shadow sampling must not correlate with split assignment (they
    # use different salts over the same key).
    assert _split_route(shadow_fraction=1.0).mirror_sample(b"x")
    assert not _split_route(shadow_fraction=0.0).mirror_sample(b"x")


def test_version_of_maps_backends():
    r = _split_route()
    assert r.version_of("a:1") == "v1"
    assert r.version_of("c:1") == "v2"
    assert r.version_of("nope:1") == ""


def test_stable_hash01_range_and_salt():
    xs = [stable_hash01(f"k{i}".encode()) for i in range(100)]
    assert all(0.0 <= x < 1.0 for x in xs)
    assert stable_hash01(b"k", b"a:") != stable_hash01(b"k", b"b:")


def test_route_annotation_validation():
    def _svc(spec):
        return {"metadata": {"name": "s", "annotations": {
            GATEWAY_ROUTE_ANNOTATION: yaml.safe_dump(spec)}}}

    base = {"name": "r", "prefix": "/m/", "service": "a:1",
            "backends": [{"service": "a:1"}, {"service": "b:1"}]}
    # splits without the hash-split strategy: rejected.
    assert routes_from_service(_svc({
        **base, "splits": [{"version": "v1", "weight": 1,
                            "backends": ["a:1"]}]})) == []
    # hash-split without splits: rejected.
    assert routes_from_service(_svc(
        {**base, "strategy": "hash-split"})) == []
    # Duplicate split versions: rejected.
    assert routes_from_service(_svc({
        **base, "strategy": "hash-split",
        "splits": [{"version": "v1", "weight": 1, "backends": ["a:1"]},
                   {"version": "v1", "weight": 1,
                    "backends": ["b:1"]}]})) == []
    # Bad shadow_fraction: rejected.
    assert routes_from_service(_svc(
        {**base, "shadow_fraction": 1.5})) == []
    # A valid hash-split route parses with its splits intact.
    routes = routes_from_service(_svc({
        **base, "strategy": "hash-split",
        "shadow_fraction": 0.5,
        "splits": [{"version": "v1", "weight": 90,
                    "backends": ["a:1"]},
                   {"version": "v2", "weight": 10,
                    "backends": ["b:1"]}]}))
    assert len(routes) == 1
    assert routes[0].splits == (("v1", 90.0, ("a:1",)),
                                ("v2", 10.0, ("b:1",)))
    assert routes[0].shadow_fraction == 0.5
