"""Cluster-scheduler tests: capacity model, fair queue, all-or-nothing
gang admission, priority preemption, and the seeded chaos churn soak.

Fast tier: pure-policy units (capacity/queue), controller rounds driven
synchronously against the fake apiserver (``reconcile_all`` = one
scheduling round), and a property-style test over randomized job mixes
asserting no reconcile interleaving ever yields a partially placed gang.

``-m chaos`` tier (also slow, excluded from tier-1): the churn soak —
seeded apiserver faults + node kills + scheduler-initiated evictions
through the real FakeKubelet SIGTERM path while checkpointing train jobs
are admitted, preempted, requeued and resumed, with final losses
byte-equal to an undisturbed reference run.
"""

from __future__ import annotations

import datetime
import json
import os
import random
import re
import time

import pytest

from kubeflow_tpu.apis import jobs as jobs_api
from kubeflow_tpu.apis import scheduling as sched_api
from kubeflow_tpu.k8s import objects as k8s
from kubeflow_tpu.k8s.chaos import ChaosApiServer
from kubeflow_tpu.k8s.fake import FakeApiServer
from kubeflow_tpu.k8s.kubelet import FakeKubelet
from kubeflow_tpu.operators.jobs import JobController
from kubeflow_tpu.scheduler.capacity import ClusterCapacity, ThroughputBook
from kubeflow_tpu.scheduler.controller import SchedulerController
from kubeflow_tpu.scheduler.queue import QueueEntry, order_queue

NS = "kubeflow"

CHAOS_SEEDS = [int(s) for s in
               os.environ.get("CHAOS_SEEDS", "0,1,2").split(",")]


def _node(name, accel="v5e", slice_id="v5e-0", topo="2x4", **kw):
    return k8s.node(name, labels={
        sched_api.NODE_ACCEL_LABEL: accel,
        sched_api.NODE_TOPO_LABEL: topo,
        sched_api.NODE_SLICE_LABEL: slice_id,
    }, tpu_chips=4, **kw)


def _add_slice(api, accel, slice_id, hosts):
    names = [f"{slice_id}-h{i}" for i in range(hosts)]
    for n in names:
        api.create(_node(n, accel=accel, slice_id=slice_id))
    return names


def _job(name, replicas=1, priority=None, queue=None, accelerator=None,
         profile=None, preemptible=None, command=None, kind="JaxJob",
         grace=None):
    spec: dict = {
        "replicaSpecs": {
            "Worker": {
                "replicas": replicas,
                "restartPolicy": "OnFailure",
                "template": {"spec": {"containers": [
                    {"name": "main", "image": "train:latest",
                     **({"command": command} if command else {})}
                ]}},
            },
        },
    }
    if grace is not None:
        spec["replicaSpecs"]["Worker"]["template"]["spec"][
            "terminationGracePeriodSeconds"] = grace
    if priority is not None:
        spec["priority"] = priority
    if queue is not None:
        spec["queue"] = queue
    if accelerator is not None:
        spec["tpu"] = {"accelerator": accelerator}
    if profile is not None:
        spec["profile"] = profile
    if preemptible is not None:
        spec["preemptible"] = preemptible
    return {"apiVersion": jobs_api.JOBS_API_VERSION, "kind": kind,
            "metadata": {"name": name, "namespace": NS}, "spec": spec}


def _set_pod_phase(api, pod_name, phase):
    pod = api.get("v1", "Pod", pod_name, NS)
    pod.setdefault("status", {})["phase"] = phase
    api.update_status(pod)


def _get_job(api, name, kind="JaxJob"):
    return api.get(jobs_api.JOBS_API_VERSION, kind, name, NS)


def _sched_state(api, name, kind="JaxJob"):
    return _get_job(api, name, kind).get("status", {}).get(
        "scheduling", {}).get("state")


def _pods_of(api, name):
    return api.list("v1", "Pod", NS,
                    label_selector={"kubeflow-tpu.org/job-name": name})


@pytest.fixture()
def cluster(api):
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    api.apply(sched_api.scheduling_policy_crd())
    api.create(sched_api.scheduling_policy(
        namespace=NS,
        preemption={"requeueBackoffSeconds": 0, "gracePeriodSeconds": 1},
    ))
    return api, SchedulerController(api), JobController(api, "JaxJob")


# ---------------------------------------------------------------------------
# capacity model
# ---------------------------------------------------------------------------


def test_capacity_pools_and_slices_from_nodes():
    nodes = [
        _node("a0", slice_id="v5e-0"), _node("a1", slice_id="v5e-0"),
        _node("b0", accel="v5p", slice_id="v5p-0", topo="4x4"),
        _node("dead", slice_id="v5e-0", ready=False),
        _node("cordoned", slice_id="v5e-0", unschedulable=True),
        k8s.node("cpu-only"),  # no accelerator label: not TPU capacity
    ]
    cap = ClusterCapacity.from_nodes(nodes)
    pools = cap.pools()
    assert set(pools) == {"v5e", "v5p"}
    (v5e,) = pools["v5e"]
    assert v5e.nodes == ["a0", "a1"]  # dead + cordoned excluded
    assert v5e.chips_per_host == 4
    assert v5e.topology == "2x4"
    assert cap.largest_slice() == 2
    assert cap.largest_slice("v5p") == 1


def test_capacity_reserve_is_all_or_nothing():
    cap = ClusterCapacity.from_nodes(
        [_node(f"h{i}") for i in range(3)])
    (sl,) = cap.slices
    cap.occupy(["h0", "h1"], "other")
    with pytest.raises(ValueError):
        cap.reserve(sl, 2, "me")  # only 1 free: nothing must be claimed
    assert cap.free_hosts(sl) == ["h2"]
    assert cap.reserve(sl, 1, "me") == ["h2"]
    cap.release("other")
    assert len(cap.free_hosts(sl)) == 2
    assert not cap.feasible(3)  # h2 still held by "me"
    assert cap.ever_fits(3) and not cap.ever_fits(4)


def test_throughput_book_prefers_measured_faster_pool():
    book = ThroughputBook({"bert": {"v5e": 10.0, "v5p": 40.0}})
    assert book.score("bert", "v5p") == 1.0
    assert book.score("bert", "v5e") == pytest.approx(0.25)
    # Unknown accelerator is placeable but never favored.
    assert book.throughput("bert", "tpu9000") == 1.0
    # Unknown profile falls back to the default table.
    assert book.score(None, "v5p") == 1.0


def test_throughput_book_from_bench_files(tmp_path):
    """Profiles load from bench-format files: the config's leading token
    names the profile, tokens/s/chip is the throughput the Gavel scoring
    normalizes. The file is SYNTHETIC — round numbers in the shape a
    trial prints, measured nowhere."""
    bench_file = tmp_path / "synthetic_bench.json"
    bench_file.write_text(json.dumps({"parsed": {
        "config": "flagship-1b bs4 seq2048 adafactor bf16 x1chip",
        "tokens_per_sec_per_chip": 20000.0,
        "deep_config": "flagship-deep bs32 seq256 adafactor bf16 x1chip",
        "deep_tokens_per_sec_per_chip": 10000.0,
    }}))
    book = ThroughputBook.from_bench_files(
        {"v5e": str(bench_file)},
        extra={"flagship-1b": {"v5p": 1e6}})
    tput = book.throughput("flagship-1b", "v5e")
    assert tput == 20000.0  # the file's number, not the 1.0 fallback
    assert book.score("flagship-1b", "v5p") == 1.0  # extra table merged
    assert book.score("flagship-1b", "v5e") == pytest.approx(
        tput / 1e6)
    # The deep-model twin config registers too.
    assert book.throughput("flagship-deep", "v5e") > 1000
    # Missing files degrade to defaults instead of raising.
    fallback = ThroughputBook.from_bench_files({"v5e": "/nonexistent"})
    assert fallback.score(None, "v5e") > 0


# ---------------------------------------------------------------------------
# queue ordering
# ---------------------------------------------------------------------------


def _entry(name, priority=0, queue="default", hosts=1, queued_ago=0.0,
           now=None, eligible_in=None):
    now = now or datetime.datetime.now(datetime.timezone.utc)
    return QueueEntry(
        key=("JaxJob", NS, name), priority=priority, queue=queue,
        hosts=hosts,
        queued_at=now - datetime.timedelta(seconds=queued_ago),
        eligible_at=(now + datetime.timedelta(seconds=eligible_in)
                     if eligible_in else None),
    )


def test_order_queue_priority_then_fifo():
    now = datetime.datetime.now(datetime.timezone.utc)
    got = order_queue(
        [_entry("old-low", 0, queued_ago=50, now=now),
         _entry("high", 5, queued_ago=1, now=now),
         _entry("older-high", 5, queued_ago=2, now=now)],
        now, aging_seconds=0, queue_weights={}, used_share={})
    assert [e.key[2] for e in got] == ["older-high", "high", "old-low"]


def test_order_queue_weighted_fair_share():
    now = datetime.datetime.now(datetime.timezone.utc)
    got = order_queue(
        [_entry("hog-high", 9, queue="hog", now=now),
         _entry("starved-low", 0, queue="quiet", now=now)],
        now, aging_seconds=0,
        queue_weights={"hog": 1.0, "quiet": 1.0},
        used_share={"hog": 8.0})  # hog already runs 8 hosts
    assert [e.key[2] for e in got] == ["starved-low", "hog-high"]


def test_order_queue_aging_promotes_starved_entry():
    now = datetime.datetime.now(datetime.timezone.utc)
    young_high = _entry("young-high", 5, queued_ago=1, now=now)
    starved_low = _entry("starved-low", 0, queued_ago=600, now=now)
    # Without aging the high-priority entry wins forever.
    got = order_queue([young_high, starved_low], now, aging_seconds=0,
                      queue_weights={}, used_share={})
    assert got[0].key[2] == "young-high"
    # 100s of wait per point: 600s waited -> effective 6 > 5.
    got = order_queue([young_high, starved_low], now, aging_seconds=100,
                      queue_weights={}, used_share={})
    assert got[0].key[2] == "starved-low"


def test_order_queue_backoff_parks_entry_behind_eligible():
    now = datetime.datetime.now(datetime.timezone.utc)
    got = order_queue(
        [_entry("preempted-high", 9, eligible_in=30, now=now),
         _entry("low", 0, now=now)],
        now, aging_seconds=0, queue_weights={}, used_share={})
    assert [e.key[2] for e in got] == ["low", "preempted-high"]


# ---------------------------------------------------------------------------
# admission (controller rounds against the fake apiserver)
# ---------------------------------------------------------------------------


def test_admission_pins_gang_to_one_slice(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _add_slice(api, "v5e", "v5e-1", 1)
    api.create(_job("gang", replicas=2, priority=1))
    sched.reconcile_all()
    jc.reconcile_all()

    job = _get_job(api, "gang")
    decided = sched_api.placement(job)
    assert decided["pool"] == "v5e" and decided["slice"] == "v5e-0"
    assert decided["nodes"] == ["v5e-0-h0", "v5e-0-h1"]
    assert job["status"]["scheduling"]["state"] == sched_api.STATE_ADMITTED
    pods = _pods_of(api, "gang")
    assert sorted(p["spec"]["nodeName"] for p in pods) == decided["nodes"]
    for p in pods:
        assert p["metadata"]["annotations"][sched_api.ANN_SLICE] == "v5e-0"
        sel = p["spec"]["nodeSelector"]
        assert sel[sched_api.NODE_ACCEL_LABEL] == "v5e"
        assert sel[sched_api.NODE_TOPO_LABEL] == "2x4"


def test_admission_prefers_measured_faster_pool(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _add_slice(api, "v5p", "v5p-0", 2)
    pol = api.get(sched_api.SCHEDULING_API_VERSION,
                  sched_api.SCHEDULING_POLICY_KIND, "default", NS)
    pol["spec"]["profiles"] = {"bert": {"v5e": 10.0, "v5p": 40.0}}
    api.update(pol)
    api.create(_job("fast", replicas=2, priority=1, profile="bert"))
    sched.reconcile_all()
    assert sched_api.placement(_get_job(api, "fast"))["pool"] == "v5p"


def test_unmanaged_job_keeps_legacy_first_come_path(cluster):
    api, sched, jc = cluster
    api.create(_job("legacy", replicas=2, accelerator="v5e"))
    sched.reconcile_all()
    jc.reconcile_all()
    job = _get_job(api, "legacy")
    assert sched_api.placement(job) is None
    assert "scheduling" not in job.get("status", {})
    pods = _pods_of(api, "legacy")
    assert len(pods) == 2  # created immediately, no scheduler gate
    for p in pods:
        assert "nodeName" not in p["spec"]
        assert p["spec"]["nodeSelector"][
            sched_api.NODE_ACCEL_LABEL] == "v5e"


def test_gang_waits_for_capacity_then_admits(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    api.create(_job("first", replicas=2, priority=1))
    sched.reconcile_all()
    jc.reconcile_all()
    api.create(_job("second", replicas=2, priority=1))
    for _ in range(3):
        sched.reconcile_all()
        jc.reconcile_all()
    assert _sched_state(api, "second") == sched_api.STATE_QUEUED
    assert _pods_of(api, "second") == []  # parked: zero pods, not some
    job = _get_job(api, "second")
    conds = {c["type"]: c["status"]
             for c in job["status"].get("conditions", [])}
    assert conds.get(sched_api.COND_QUEUED) == "True"

    for pod in _pods_of(api, "first"):
        _set_pod_phase(api, pod["metadata"]["name"], "Succeeded")
    jc.reconcile_all()
    sched.reconcile_all()
    jc.reconcile_all()
    assert _sched_state(api, "second") == sched_api.STATE_ADMITTED
    assert len(_pods_of(api, "second")) == 2


def test_unschedulable_condition_and_recovery(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    api.create(_job("toobig", replicas=3, priority=1))
    sched.reconcile_all()
    job = _get_job(api, "toobig")
    assert job["status"]["scheduling"]["state"] == \
        sched_api.STATE_UNSCHEDULABLE
    conds = {c["type"]: c for c in job["status"]["conditions"]}
    assert conds[sched_api.COND_UNSCHEDULABLE]["status"] == "True"
    assert "largest is 2" in conds[sched_api.COND_UNSCHEDULABLE]["message"]
    assert _pods_of(api, "toobig") == []

    # Matching capacity appears: the job is admitted, not stuck.
    _add_slice(api, "v5e", "v5e-1", 3)
    sched.reconcile_all()
    jc.reconcile_all()
    job = _get_job(api, "toobig")
    assert job["status"]["scheduling"]["state"] == sched_api.STATE_ADMITTED
    conds = {c["type"]: c["status"] for c in job["status"]["conditions"]}
    assert conds[sched_api.COND_UNSCHEDULABLE] == "False"
    assert len(_pods_of(api, "toobig")) == 3


def test_accelerator_constraint_restricts_pools(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _add_slice(api, "v5p", "v5p-0", 2)
    api.create(_job("pinned", replicas=2, priority=1, accelerator="v5p"))
    sched.reconcile_all()
    assert sched_api.placement(_get_job(api, "pinned"))["pool"] == "v5p"
    # And an accelerator that exists nowhere is Unschedulable, not queued.
    api.create(_job("nowhere", replicas=1, priority=1,
                    accelerator="v9x"))
    sched.reconcile_all()
    assert _sched_state(api, "nowhere") == sched_api.STATE_UNSCHEDULABLE


def test_starved_low_priority_eventually_admitted_by_aging(cluster):
    """A low-priority gang behind a stream of high-priority arrivals is
    eventually admitted: aging lifts its effective priority past new
    high-priority submissions."""
    api, sched, jc = cluster
    pol = api.get(sched_api.SCHEDULING_API_VERSION,
                  sched_api.SCHEDULING_POLICY_KIND, "default", NS)
    pol["spec"]["agingSeconds"] = 0.02  # 20ms of wait per priority point
    api.update(pol)
    _add_slice(api, "v5e", "v5e-0", 1)

    api.create(_job("hog", replicas=1, priority=5))
    sched.reconcile_all()
    jc.reconcile_all()
    api.create(_job("meek", replicas=1, priority=0))
    sched.reconcile_all()  # stamps meek's queuedAt
    assert _sched_state(api, "meek") == sched_api.STATE_QUEUED
    time.sleep(0.3)  # meek ages past priority 5+

    # A fresh high-priority arrival and a freed slice: the aged
    # low-priority gang must win the slot.
    api.create(_job("fresh-high", replicas=1, priority=5))
    for pod in _pods_of(api, "hog"):
        _set_pod_phase(api, pod["metadata"]["name"], "Succeeded")
    jc.reconcile_all()
    sched.reconcile_all()
    jc.reconcile_all()
    assert _sched_state(api, "meek") == sched_api.STATE_ADMITTED
    assert _sched_state(api, "fresh-high") == sched_api.STATE_QUEUED


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------


def _run_gang(api, sched, jc, name, replicas=2, **kw):
    api.create(_job(name, replicas=replicas, **kw))
    sched.reconcile_all()
    jc.reconcile_all()
    for pod in _pods_of(api, name):
        _set_pod_phase(api, pod["metadata"]["name"], "Running")
    jc.reconcile_all()


def test_priority_preemption_within_bounded_rounds(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _run_gang(api, sched, jc, "victim", priority=0)
    api.create(_job("vip", replicas=2, priority=10))

    # Bounded: one round evicts, one job-controller pass accounts +
    # deletes, the next round admits the preemptor.
    sched.reconcile_all()
    victim = _get_job(api, "victim")
    assert victim["metadata"]["annotations"][
        sched_api.ANN_PREEMPTED_BY] == "JaxJob/kubeflow/vip"
    assert sched_api.placement(victim) is None
    assert victim["status"]["scheduling"]["state"] == \
        sched_api.STATE_PREEMPTED
    for pod in _pods_of(api, "victim"):
        assert pod["metadata"]["annotations"][
            sched_api.ANN_PREEMPTED_BY] == "JaxJob/kubeflow/vip"
        assert pod["status"]["phase"] == "Failed"
        assert any(c["type"] == "DisruptionTarget"
                   and c["status"] == "True"
                   for c in pod["status"]["conditions"])

    jc.reconcile_all()
    victim = _get_job(api, "victim")
    assert victim["status"].get("preemptionCount") == 1
    assert victim["status"].get("restartCount", 0) == 0
    assert _pods_of(api, "victim") == []

    sched.reconcile_all()
    jc.reconcile_all()
    assert _sched_state(api, "vip") == sched_api.STATE_ADMITTED
    assert len(_pods_of(api, "vip")) == 2

    # Victim requeues (backoff 0) and is re-admitted once vip finishes.
    for pod in _pods_of(api, "vip"):
        _set_pod_phase(api, pod["metadata"]["name"], "Succeeded")
    jc.reconcile_all()
    sched.reconcile_all()
    jc.reconcile_all()
    victim = _get_job(api, "victim")
    assert victim["status"]["scheduling"]["state"] == \
        sched_api.STATE_ADMITTED
    assert victim["metadata"]["annotations"].get(
        sched_api.ANN_PREEMPTED_BY) is None  # cleared on re-admission
    assert len(_pods_of(api, "victim")) == 2


def test_preemption_respects_preemptible_false_and_priority_gap(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _run_gang(api, sched, jc, "pinned", priority=0, preemptible=False)

    api.create(_job("equal", replicas=2, priority=0))
    api.create(_job("vip", replicas=2, priority=10))
    for _ in range(3):
        sched.reconcile_all()
        jc.reconcile_all()
    # Neither the equal-priority job nor the VIP evicted the pinned gang.
    assert sched_api.placement(_get_job(api, "pinned")) is not None
    assert _get_job(api, "pinned")["status"].get("preemptionCount") is None
    assert _sched_state(api, "vip") == sched_api.STATE_QUEUED
    assert all(p["status"]["phase"] == "Running"
               for p in _pods_of(api, "pinned"))


def test_preemption_picks_fewest_victims_slice(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _add_slice(api, "v5e", "v5e-1", 2)
    _run_gang(api, sched, jc, "one-gang", replicas=2, priority=0)
    _run_gang(api, sched, jc, "small-a", replicas=1, priority=0)
    _run_gang(api, sched, jc, "small-b", replicas=1, priority=0)

    api.create(_job("vip", replicas=2, priority=10))
    sched.reconcile_all()
    # Evicting the single 2-host gang frees a whole slice with ONE
    # victim; the two 1-host gangs on the other slice survive.
    assert _sched_state(api, "one-gang") == sched_api.STATE_PREEMPTED
    assert sched_api.placement(_get_job(api, "small-a")) is not None
    assert sched_api.placement(_get_job(api, "small-b")) is not None


def test_node_loss_revokes_placement_and_reschedules(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _run_gang(api, sched, jc, "mobile", replicas=2, priority=1)
    assert sched_api.placement(_get_job(api, "mobile"))["slice"] == "v5e-0"

    # Node killed: object deleted, pods die with the host.
    api.delete("v1", "Node", "v5e-0-h0")
    for pod in _pods_of(api, "mobile"):
        _set_pod_phase(api, pod["metadata"]["name"], "Failed")
    sched.reconcile_all()  # revokes: reserved host is gone
    job = _get_job(api, "mobile")
    assert sched_api.placement(job) is None
    # Requeued — and since no remaining slice can hold the gang, the
    # distinct Unschedulable surface appears rather than silent queueing.
    assert job["status"]["scheduling"]["state"] == \
        sched_api.STATE_UNSCHEDULABLE
    jc.reconcile_all()  # gang cleanup, no recreate while unplaced
    assert _pods_of(api, "mobile") == []

    # Replacement capacity arrives: the gang moves wholesale.
    _add_slice(api, "v5e", "v5e-1", 2)
    sched.reconcile_all()
    jc.reconcile_all()
    decided = sched_api.placement(_get_job(api, "mobile"))
    assert decided["slice"] == "v5e-1"
    assert len(_pods_of(api, "mobile")) == 2


# ---------------------------------------------------------------------------
# elastic: shrink-before-preempt, grow into idle capacity
# ---------------------------------------------------------------------------


def _elastic_job(name, replicas=1, min_r=1, max_r=2, **kw):
    job = _job(name, replicas=replicas, **kw)
    job["spec"]["elastic"] = {"minReplicas": min_r, "maxReplicas": max_r}
    return job


def _granted(api, name):
    decided = sched_api.placement(_get_job(api, name))
    return len(decided["nodes"]) if decided else None


def test_elastic_admission_extends_grant_to_max(cluster):
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 3)
    api.create(_elastic_job("stretchy", min_r=1, max_r=3, priority=1))
    sched.reconcile_all()
    jc.reconcile_all()
    job = _get_job(api, "stretchy")
    decided = sched_api.placement(job)
    assert decided["nodes"] == ["v5e-0-h0", "v5e-0-h1", "v5e-0-h2"]
    assert decided["elastic"] == {"granted": 3, "min": 1, "max": 3}
    assert job["status"]["scheduling"]["granted"] == 3
    # One pod (the process count), seated on the grant's first host.
    pods = _pods_of(api, "stretchy")
    assert len(pods) == 1
    assert pods[0]["spec"]["nodeName"] == "v5e-0-h0"


def test_elastic_degraded_admission_at_partial_capacity(cluster):
    """Only 1 of 2 hosts free: the elastic gang admits at its floor now
    instead of queueing for the max."""
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _run_gang(api, sched, jc, "occupant", replicas=1, priority=1)
    api.create(_elastic_job("flex", min_r=1, max_r=2, priority=1))
    sched.reconcile_all()
    assert _sched_state(api, "flex") == sched_api.STATE_ADMITTED
    assert _granted(api, "flex") == 1


def test_shrink_before_preempt_seats_vip_without_killing(cluster):
    """The PR's core scheduler behavior: a queued gang that cannot fit
    SHRINKS an elastic victim (placement rewrite, pods untouched, job
    still Admitted/Running) instead of evicting it — and the preemptor
    admits in the SAME round."""
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    api.create(_elastic_job("victim", min_r=1, max_r=2, priority=0))
    sched.reconcile_all()
    jc.reconcile_all()
    assert _granted(api, "victim") == 2
    for pod in _pods_of(api, "victim"):
        _set_pod_phase(api, pod["metadata"]["name"], "Running")
    jc.reconcile_all()
    pod_names = {p["metadata"]["name"] for p in _pods_of(api, "victim")}

    api.create(_job("vip", replicas=1, priority=10))
    sched.reconcile_all()

    victim = _get_job(api, "victim")
    decided = sched_api.placement(victim)
    assert decided is not None, "victim must stay placed"
    assert decided["nodes"] == ["v5e-0-h0"]
    assert decided["elastic"]["granted"] == 1
    assert victim["status"]["scheduling"]["state"] == \
        sched_api.STATE_ADMITTED
    assert victim["status"]["scheduling"]["granted"] == 1
    assert victim["status"]["scheduling"].get("resizedAt")
    # No eviction artifacts anywhere.
    assert victim["metadata"]["annotations"].get(
        sched_api.ANN_PREEMPTED_BY) is None
    assert victim["status"].get("preemptionCount") is None
    # VIP seated on the released host in the same round.
    assert _sched_state(api, "vip") == sched_api.STATE_ADMITTED
    assert sched_api.placement(_get_job(api, "vip"))["nodes"] == \
        ["v5e-0-h1"]

    jc.reconcile_all()
    # The victim's pod set is untouched — a shrink must never churn pods.
    after = {p["metadata"]["name"] for p in _pods_of(api, "victim")}
    assert after == pod_names
    assert all(p["status"]["phase"] == "Running"
               for p in _pods_of(api, "victim"))
    assert len(_pods_of(api, "vip")) == 1

    body = OPERATOR_METRICS_RENDER()
    assert re.search(r"scheduler_shrinks_total \d", body)


def OPERATOR_METRICS_RENDER():
    from kubeflow_tpu.operators.base import OPERATOR_METRICS

    return OPERATOR_METRICS.render()


def test_shrink_at_floor_falls_back_to_pr10_preemption(cluster):
    """An elastic job already at its floor has nothing to reclaim: the
    scheduler preempts exactly as PR 10 — lowest-priority preemptible
    victim evicted with the full mark-then-evict sequence."""
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    api.create(_elastic_job("atfloor", min_r=2, max_r=2, priority=5))
    sched.reconcile_all()
    jc.reconcile_all()
    assert _granted(api, "atfloor") == 2

    api.create(_job("vip", replicas=2, priority=10))
    sched.reconcile_all()
    victim = _get_job(api, "atfloor")
    assert sched_api.placement(victim) is None
    assert victim["metadata"]["annotations"][
        sched_api.ANN_PREEMPTED_BY] == "JaxJob/kubeflow/vip"
    assert victim["status"]["scheduling"]["state"] == \
        sched_api.STATE_PREEMPTED


def test_shrink_reclaims_only_down_to_floor(cluster):
    """minReplicas bounds the reclaim: a 3-host grant with min 2 gives
    up exactly one host; a 2-host preemptor cannot be seated by shrink
    alone and falls back to eviction of OTHER victims (never the one
    just shrunk — one round disturbs a victim at most once)."""
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 3)
    api.create(_elastic_job("bounded", min_r=2, max_r=3, priority=0))
    sched.reconcile_all()
    assert _granted(api, "bounded") == 3

    api.create(_job("one", replicas=1, priority=10))
    sched.reconcile_all()
    assert _granted(api, "bounded") == 2  # shrink freed exactly 1
    assert _sched_state(api, "one") == sched_api.STATE_ADMITTED

    # Next arrival needs 2: bounded is at floor, only eviction remains —
    # and it evicts bounded (the only preemptible victim), never having
    # shrunk it in the same round.
    api.create(_job("two", replicas=2, priority=20))
    sched.reconcile_all()
    bounded = _get_job(api, "bounded")
    assert bounded["status"]["scheduling"]["state"] == \
        sched_api.STATE_PREEMPTED


def test_grow_into_idle_capacity_after_completion(cluster):
    """A completed neighbor frees hosts and nothing is queued: the
    elastic job grows back toward max (placement rewrite, granted up)."""
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _run_gang(api, sched, jc, "neighbor", replicas=1, priority=1)
    api.create(_elastic_job("flex", min_r=1, max_r=2, priority=1))
    sched.reconcile_all()
    assert _granted(api, "flex") == 1

    for pod in _pods_of(api, "neighbor"):
        _set_pod_phase(api, pod["metadata"]["name"], "Succeeded")
    jc.reconcile_all()
    sched.reconcile_all()
    job = _get_job(api, "flex")
    decided = sched_api.placement(job)
    assert len(decided["nodes"]) == 2
    assert decided["elastic"]["granted"] == 2
    assert job["status"]["scheduling"]["granted"] == 2
    body = OPERATOR_METRICS_RENDER()
    assert re.search(r"scheduler_grows_total \d", body)


def test_grow_yields_to_queued_gang(cluster):
    """Freed capacity goes to the queued gang, not to growing a running
    elastic job past it — grow takes only genuinely idle hosts."""
    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _run_gang(api, sched, jc, "neighbor", replicas=1, priority=1)
    api.create(_elastic_job("flex", min_r=1, max_r=2, priority=1))
    sched.reconcile_all()
    assert _granted(api, "flex") == 1
    api.create(_job("queued", replicas=1, priority=1))
    sched.reconcile_all()
    assert _sched_state(api, "queued") == sched_api.STATE_QUEUED

    for pod in _pods_of(api, "neighbor"):
        _set_pod_phase(api, pod["metadata"]["name"], "Succeeded")
    jc.reconcile_all()
    sched.reconcile_all()
    # The queued gang got the host; flex stays at 1.
    assert _sched_state(api, "queued") == sched_api.STATE_ADMITTED
    assert _granted(api, "flex") == 1


def test_grow_delay_quiet_period(cluster):
    """growDelaySeconds: a just-shrunk job does not bounce straight
    back when the preemptor finishes quickly."""
    api, sched, jc = cluster
    pol = api.get(sched_api.SCHEDULING_API_VERSION,
                  sched_api.SCHEDULING_POLICY_KIND, "default", NS)
    pol["spec"]["elastic"] = {"growDelaySeconds": 3600}
    api.update(pol)
    _add_slice(api, "v5e", "v5e-0", 2)
    api.create(_elastic_job("calm", min_r=1, max_r=2, priority=0))
    sched.reconcile_all()
    jc.reconcile_all()
    api.create(_job("vip", replicas=1, priority=10))
    sched.reconcile_all()
    assert _granted(api, "calm") == 1
    for pod in _pods_of(api, "vip"):
        _set_pod_phase(api, pod["metadata"]["name"], "Succeeded")
    jc.reconcile_all()
    sched.reconcile_all()
    sched.reconcile_all()
    assert _granted(api, "calm") == 1  # inside the quiet period


def test_shrink_disabled_by_policy_falls_back_to_preempt(cluster):
    api, sched, jc = cluster
    pol = api.get(sched_api.SCHEDULING_API_VERSION,
                  sched_api.SCHEDULING_POLICY_KIND, "default", NS)
    pol["spec"]["elastic"] = {"shrinkBeforePreempt": False}
    api.update(pol)
    _add_slice(api, "v5e", "v5e-0", 2)
    api.create(_elastic_job("victim", min_r=1, max_r=2, priority=0))
    sched.reconcile_all()
    api.create(_job("vip", replicas=1, priority=10))
    sched.reconcile_all()
    victim = _get_job(api, "victim")
    assert victim["status"]["scheduling"]["state"] == \
        sched_api.STATE_PREEMPTED


def test_elastic_spec_validation():
    from kubeflow_tpu.apis.jobs import JobValidationError, validate_job

    ok = _elastic_job("ok", replicas=1, min_r=1, max_r=4, priority=1)
    validate_job(ok)
    bad_range = _elastic_job("bad", min_r=3, max_r=2, priority=1)
    with pytest.raises(JobValidationError, match="invalid"):
        validate_job(bad_range)
    below_pods = _elastic_job("bad2", replicas=2, min_r=1, max_r=4,
                              priority=1)
    with pytest.raises(JobValidationError, match="below the gang"):
        validate_job(below_pods)
    garbage = _job("bad3", priority=1)
    garbage["spec"]["elastic"] = {"minReplicas": "many"}
    with pytest.raises(JobValidationError):
        validate_job(garbage)
    # Malformed elastic blocks read as non-elastic for the scheduler.
    assert sched_api.elastic_spec(garbage) is None
    assert sched_api.elastic_spec(ok) == {"min": 1, "max": 4}


class _PatchRecorder:
    """Transparent client proxy logging annotation patches — the shrink
    vs evict property must be checked at patch granularity (an eviction
    in the same round would overwrite the shrink in any before/after
    snapshot)."""

    def __init__(self, inner):
        self._inner = inner
        self.patches: list[tuple[str, dict]] = []

    def patch(self, api_version, kind, name, body, namespace=None):
        self.patches.append((name, body))
        return self._inner.patch(api_version, kind, name, body, namespace)

    def __getattr__(self, attr):
        return getattr(self._inner, attr)


def test_property_shrink_grow_rounds_keep_invariants():
    """Randomized elastic/fixed job mixes over randomized rounds: a
    round never both resizes and evicts the same victim, grants stay
    inside [floor, max], pods always sit on the grant's prefix, hosts
    are never double-booked, and non-elastic gangs keep the PR-10
    all-or-nothing contract."""
    for seed in (0, 1, 2):
        rng = random.Random(seed)
        api = FakeApiServer()
        api.ensure_namespace(NS)
        for crd in jobs_api.all_job_crds():
            api.apply(crd)
        api.apply(sched_api.scheduling_policy_crd())
        api.create(sched_api.scheduling_policy(
            namespace=NS, preemption={"requeueBackoffSeconds": 0}))
        slices = {"v5e-0": _add_slice(api, "v5e", "v5e-0", 4),
                  "v5e-1": _add_slice(api, "v5e", "v5e-1", 3)}
        recorder = _PatchRecorder(api)
        sched = SchedulerController(recorder)
        jc = JobController(api, "JaxJob")

        jobs = {}
        for i in range(7):
            name = f"j{i}"
            if rng.random() < 0.5:
                max_r = rng.randint(2, 4)
                jobs[name] = {"pods": 1, "elastic": (1, max_r)}
                api.create(_elastic_job(name, replicas=1, min_r=1,
                                        max_r=max_r,
                                        priority=rng.randint(0, 10)))
            else:
                pods = rng.randint(1, 3)
                jobs[name] = {"pods": pods, "elastic": None}
                api.create(_job(name, replicas=pods,
                                priority=rng.randint(0, 10)))

        def check_round_patches():
            """A resize rewrite ({placement: str} alone) and an evict
            ({placement: None, preempted-by: str}) must never target the
            same job inside one round."""
            resized_jobs, evicted_jobs = set(), set()
            for name, body in recorder.patches:
                ann = body.get("metadata", {}).get("annotations")
                if not ann or sched_api.ANN_PLACEMENT not in ann:
                    continue
                if (ann[sched_api.ANN_PLACEMENT] is None
                        and ann.get(sched_api.ANN_PREEMPTED_BY)):
                    evicted_jobs.add(name)
                elif (ann[sched_api.ANN_PLACEMENT] is not None
                      and sched_api.ANN_PREEMPTED_BY not in ann):
                    resized_jobs.add(name)
            both = resized_jobs & evicted_jobs
            assert not both, (
                f"seed={seed}: jobs resized AND evicted in one round: "
                f"{both}")

        def check_state():
            assignments = {}
            for name, info in jobs.items():
                job = _get_job(api, name)
                state = job.get("status", {}).get("state")
                decided = sched_api.placement(job)
                pods = _pods_of(api, name)
                if info["elastic"]:
                    lo, hi = info["elastic"]
                    floor = max(lo, info["pods"])
                    if decided is not None:
                        granted = len(decided["nodes"])
                        assert floor <= granted <= hi, (
                            f"seed={seed}: {name} grant {granted} "
                            f"outside [{floor}, {hi}]")
                        for pod in pods:
                            if pod.get("status", {}).get("phase") in (
                                    "Succeeded", "Failed"):
                                continue
                            assert pod["spec"]["nodeName"] in \
                                decided["nodes"][:info["pods"]], (
                                f"seed={seed}: {name} pod off the "
                                "grant prefix")
                else:
                    assert len(pods) in (0, info["pods"]), (
                        f"seed={seed}: {name} partially placed")
                if decided is None:
                    continue
                assert set(decided["nodes"]) <= set(
                    slices[decided["slice"]])
                if state in ("Succeeded", "Failed"):
                    continue
                for node in decided["nodes"]:
                    assert node not in assignments, (
                        f"seed={seed}: {node} double-booked by "
                        f"{assignments[node]} and {name}")
                    assignments[node] = name

        for _ in range(40):
            op = rng.random()
            if op < 0.4:
                recorder.patches.clear()
                sched.reconcile_all()
                check_round_patches()
            elif op < 0.7:
                jc.reconcile_all()
            else:
                placed = [n for n in jobs
                          if sched_api.placement(_get_job(api, n))
                          and _get_job(api, n).get("status", {}).get(
                              "state") not in ("Succeeded", "Failed")]
                if placed:
                    done = rng.choice(placed)
                    for pod in _pods_of(api, done):
                        _set_pod_phase(api, pod["metadata"]["name"],
                                       "Succeeded")
            check_state()


# ---------------------------------------------------------------------------
# all-or-nothing: property-style over randomized mixes + interleavings
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_property_no_interleaving_partially_places_a_gang(seed):
    """Randomized job mixes under randomized reconcile interleavings,
    completions, preemptions and node churn: at every step, every gang
    has 0 or ALL of its pods, placements never overlap hosts, and every
    placement stays inside one slice."""
    rng = random.Random(seed)
    api = FakeApiServer()
    api.ensure_namespace(NS)
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    api.apply(sched_api.scheduling_policy_crd())
    api.create(sched_api.scheduling_policy(
        namespace=NS, preemption={"requeueBackoffSeconds": 0}))
    slices = {"v5e-0": _add_slice(api, "v5e", "v5e-0", 3),
              "v5e-1": _add_slice(api, "v5e", "v5e-1", 2),
              "v5p-0": _add_slice(api, "v5p", "v5p-0", 4)}
    sched = SchedulerController(api)
    jc = JobController(api, "JaxJob")

    jobs = {}
    for i in range(8):
        name = f"j{i}"
        jobs[name] = rng.randint(1, 4)  # gang size
        api.create(_job(name, replicas=jobs[name],
                        priority=rng.randint(0, 10)))

    def check_invariants():
        assignments = {}  # node -> holder
        for name, gang in jobs.items():
            job = _get_job(api, name)
            state = job.get("status", {}).get("state")
            pods = _pods_of(api, name)
            assert len(pods) in (0, gang), (
                f"seed={seed}: gang {name} partially placed: "
                f"{len(pods)}/{gang} pods")
            decided = sched_api.placement(job)
            if decided is None:
                continue
            nodes = decided["nodes"]
            assert len(nodes) == gang
            # Whole gang inside ONE slice.
            assert set(nodes) <= set(slices[decided["slice"]]), (
                f"seed={seed}: {name} spans slices: {nodes}")
            if state in ("Succeeded", "Failed"):
                continue
            for node in nodes:
                assert node not in assignments, (
                    f"seed={seed}: host {node} double-booked by "
                    f"{assignments[node]} and {name}")
                assignments[node] = name
            for pod in pods:
                if pod.get("status", {}).get("phase") in ("Succeeded",
                                                          "Failed"):
                    continue
                assert pod["spec"]["nodeName"] in nodes

    for _ in range(50):
        op = rng.random()
        if op < 0.35:
            sched.reconcile_all()
        elif op < 0.7:
            jc.reconcile_all()
        elif op < 0.85:
            # Complete a random placed gang.
            placed = [n for n in jobs
                      if sched_api.placement(_get_job(api, n))
                      and _get_job(api, n).get("status", {}).get("state")
                      not in ("Succeeded", "Failed")]
            if placed:
                victim = rng.choice(placed)
                for pod in _pods_of(api, victim):
                    _set_pod_phase(api, pod["metadata"]["name"],
                                   "Succeeded")
        else:
            # Random pod failure (infra flake) on a placed gang.
            pods = [p for p in api.list("v1", "Pod", NS)
                    if p.get("status", {}).get("phase")
                    not in ("Succeeded", "Failed")]
            if pods:
                _set_pod_phase(
                    api, rng.choice(pods)["metadata"]["name"], "Failed")
        check_invariants()

    # Drain: everything eventually completes or is cleanly queued.
    for _ in range(30):
        sched.reconcile_all()
        jc.reconcile_all()
        placed = [n for n in jobs
                  if sched_api.placement(_get_job(api, n))
                  and _get_job(api, n).get("status", {}).get("state")
                  not in ("Succeeded", "Failed")]
        for name in placed:
            pods = _pods_of(api, name)
            if pods and len(pods) == jobs[name]:
                for pod in pods:
                    _set_pod_phase(api, pod["metadata"]["name"],
                                   "Succeeded")
        check_invariants()
    states = {n: _get_job(api, n).get("status", {}).get("state")
              for n in jobs}
    assert all(s == "Succeeded" for s in states.values()), (
        f"seed={seed}: not every gang completed: {states}")


def test_event_driven_rounds_admit_without_resync(cluster):
    """Threaded runtime: job/pod events requeue the policy key (the
    scheduler watches every job kind plus pods and nodes), so a newly
    created gang is admitted by an event-driven round, not the resync."""
    import threading

    api, sched, jc = cluster
    sched.resync_seconds = 60.0  # effectively off: events must drive it
    jc.resync_seconds = 60.0
    _add_slice(api, "v5e", "v5e-0", 2)
    threads = [threading.Thread(target=c.run, daemon=True)
               for c in (sched, jc)]
    for t in threads:
        t.start()
    try:
        api.create(_job("evented", replicas=2, priority=1))
        _wait_for(lambda: len(_pods_of(api, "evented")) == 2,
                  timeout=10.0, message="event-driven admission")
        assert _sched_state(api, "evented") == sched_api.STATE_ADMITTED
    finally:
        sched.stop()
        jc.stop()
        for t in threads:
            t.join(2)


# ---------------------------------------------------------------------------
# metrics exposition
# ---------------------------------------------------------------------------


def test_scheduler_metrics_exported_via_shared_registry(cluster):
    from kubeflow_tpu.observability.metrics import type_line
    from kubeflow_tpu.operators.base import OPERATOR_METRICS

    api, sched, jc = cluster
    _add_slice(api, "v5e", "v5e-0", 2)
    _run_gang(api, sched, jc, "metered", priority=0, queue="research")
    api.create(_job("vip", replicas=2, priority=10))
    sched.reconcile_all()
    jc.reconcile_all()
    sched.reconcile_all()

    body = OPERATOR_METRICS.render()
    assert type_line("scheduler_queue_depth", "gauge") in body
    assert type_line("scheduler_queue_wait_seconds", "histogram") in body
    assert type_line("scheduler_placement_seconds", "histogram") in body
    assert 'scheduler_admissions_total{pool="v5e"}' in body
    assert 'scheduler_preemptions_total{reason="priority"}' in body
    assert 'scheduler_requeues_total{reason="preempted"}' in body
    assert 'scheduler_queue_wait_seconds_count{queue="research"}' in body


# ---------------------------------------------------------------------------
# kubelet eviction grace (satellite)
# ---------------------------------------------------------------------------


def _wait_for(predicate, timeout=30.0, interval=0.05, message="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {message}")


def test_kubelet_evict_honors_pod_termination_grace(api, tmp_path):
    """SIGTERM is delivered and the pod's own
    terminationGracePeriodSeconds bounds the window before SIGKILL: a
    graceful pod exits 0 inside it; a stubborn pod is killed at it."""
    # Each program says, by creating its file, that its handler is in:
    # a SIGTERM before that would kill the graceful pod by signal.
    ready = {n: tmp_path / f"{n}.ready" for n in ("graceful", "stubborn")}
    graceful = ("import signal, sys, time\n"
                "signal.signal(signal.SIGTERM,"
                " lambda *a: (print('sigterm-handled', flush=True),"
                " sys.exit(0)))\n"
                f"open({str(ready['graceful'])!r}, 'w').close()\n"
                "time.sleep(120)\n")
    stubborn = ("import signal, time\n"
                "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
                f"open({str(ready['stubborn'])!r}, 'w').close()\n"
                "time.sleep(120)\n")
    for name, prog, grace in (("graceful", graceful, 30),
                              ("stubborn", stubborn, 1)):
        api.create({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": NS},
            "spec": {"terminationGracePeriodSeconds": grace,
                     "containers": [{
                         "name": "main",
                         "command": ["python", "-c", prog]}]},
        })
    kubelet = FakeKubelet(api, timeout=60)
    try:
        kubelet.step()
        _wait_for(lambda: all(
            api.get("v1", "Pod", n, NS)["status"].get("phase") == "Running"
            and ready[n].exists() for n in ready),
            message="pods running with their handlers installed")

        t0 = time.monotonic()
        assert kubelet.evict("graceful", NS)  # grace from the pod spec
        assert time.monotonic() - t0 < 25  # exited on SIGTERM, not KILL
        pod = api.get("v1", "Pod", "graceful", NS)
        assert pod["status"]["phase"] == "Failed"
        assert pod["status"]["reason"] == "Preempted"
        assert "sigterm-handled" in pod["status"]["log"]
        assert pod["status"]["containerStatuses"][0]["state"][
            "terminated"]["exitCode"] == 0
        assert any(c["type"] == "DisruptionTarget"
                   and c["status"] == "True"
                   for c in pod["status"]["conditions"])

        t0 = time.monotonic()
        assert kubelet.evict("stubborn", NS)
        took = time.monotonic() - t0
        assert 0.9 <= took < 10  # SIGKILL at the 1s pod grace
        pod = api.get("v1", "Pod", "stubborn", NS)
        assert pod["status"]["phase"] == "Failed"
        assert pod["status"]["containerStatuses"][0]["state"][
            "terminated"]["exitCode"] == 137
    finally:
        kubelet.shutdown()


# ---------------------------------------------------------------------------
# chaos churn soak (-m chaos; the PR's acceptance E2E)
# ---------------------------------------------------------------------------


def _losses_from_log(log: str) -> dict[int, str]:
    out = {}
    for line in log.splitlines():
        if line.startswith("step=") and "loss=" in line:
            parts = dict(kv.split("=") for kv in line.split() if "=" in kv)
            out[int(parts["step"])] = parts["loss"]
    return out


def _train_job(name, ck_dir, steps, *, priority=None, grace=60):
    cfg = {"model": "lm-test-tiny",
           "model_overrides": {"n_layers": 2, "d_model": 64, "d_ff": 128},
           "steps": steps, "log_every": 1, "batch_size": 4, "seq_len": 32,
           "checkpoint_every": 10, "seed": 5, "checkpoint_dir": ck_dir}
    return _job(name, replicas=1, priority=priority, grace=grace,
                command=["python", "-m", "kubeflow_tpu.train.loop",
                         json.dumps(cfg)])


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS[:1])
def test_chaos_elastic_shrink_soak_byte_equal(seed, tmp_path):
    """The elastic acceptance E2E: a VIP gang arrives while an elastic
    victim trains across the whole slice; the scheduler SHRINKS the
    victim (placement rewrite through a hostile apiserver) instead of
    killing it — the victim's live loop reshards 8→4 devices at a step
    boundary and keeps training, the VIP seats on the released host,
    BOTH jobs Succeed, the victim's pod is never restarted, and the
    victim's post-reshard losses are byte-equal to an undisturbed
    same-global-batch reference (the reshard-point checkpoint restored
    into the target mesh and replayed with no scheduler in the loop)."""
    import shutil

    from kubeflow_tpu.k8s.httpfake import serve
    from kubeflow_tpu.train import checkpoint as ckpt_lib
    from kubeflow_tpu.train.loop import RunConfig, run

    # Sized so the victim is still mid-run through admission → VIP
    # arrival → shrink → live reshard (a few seconds of remaining
    # runtime at ~15ms/step) while the WHOLE per-step loss log still
    # fits the kubelet's 64KB status.log tail — the byte-equality
    # comparison below reads every post-reshard line from it.
    steps = 400
    fake = FakeApiServer()
    fake.ensure_namespace(NS)
    for crd in jobs_api.all_job_crds():
        fake.apply(crd)
    fake.apply(sched_api.scheduling_policy_crd())
    fake.create(sched_api.scheduling_policy(
        namespace=NS,
        preemption={"requeueBackoffSeconds": 0.5,
                    "gracePeriodSeconds": 60},
        # Grow stays off so the victim reshards exactly once — the
        # byte-equality replay below anchors at that single reshard
        # point (live grow is pinned by the fast elastic tests).
        elastic={"growEnabled": False},
    ))
    _add_slice(fake, "v5e", "v5e-0", 2)

    # The victim's in-pod placement poller reads through the real HTTP
    # frontend; controllers go through the hostile chaos wrapper.
    httpd, port = serve(fake)
    chaos = ChaosApiServer(fake, seed=seed, error_rate=0.05,
                           conflict_rate=0.15,
                           error_after_create_rate=0.05,
                           latency_seconds=0.001)
    kubelet = FakeKubelet(
        fake, cpu_devices_per_pod=8, timeout=600,
        extra_env={
            "KUBEFLOW_TPU_APISERVER": f"http://127.0.0.1:{port}"})
    sched = SchedulerController(
        chaos,
        evict=lambda pod, grace: kubelet.evict(
            pod["metadata"]["name"], pod["metadata"]["namespace"],
            grace_seconds=grace))
    jc = JobController(chaos, "JaxJob")

    def tolerant(fn):
        from kubeflow_tpu.k8s.client import ApiError

        try:
            fn()
        except ApiError as e:
            if not e.transient and e.code != 409:
                raise

    def spin(predicate, deadline=300.0, message="condition"):
        end = time.monotonic() + deadline
        while time.monotonic() < end:
            kubelet.step()
            tolerant(jc.reconcile_all)
            tolerant(sched.reconcile_all)
            if predicate():
                return
            time.sleep(0.05)
        raise AssertionError(f"elastic soak timed out waiting for "
                             f"{message} (seed={seed})")

    ck = str(tmp_path / "victim")
    cfg = {"model": "lm-test-tiny",
           "model_overrides": {"n_layers": 2, "d_model": 64,
                               "d_ff": 128},
           "steps": steps, "log_every": 1, "batch_size": 8,
           "seq_len": 32, "checkpoint_every": 10 ** 9, "seed": 5,
           "checkpoint_dir": ck, "elastic_poll_steps": 1,
           "prefetch": 2}
    victim = _job("victim", replicas=1, priority=0, grace=60,
                  command=["python", "-m", "kubeflow_tpu.train.loop",
                           json.dumps(cfg)])
    victim["spec"]["elastic"] = {"minReplicas": 1, "maxReplicas": 2}

    def grant_is(n):
        def check():
            decided = sched_api.placement(_get_job(fake, "victim"))
            return bool(decided) and len(decided["nodes"]) == n
        return check

    def victim_log():
        pod = fake.get_or_none("v1", "Pod", "victim-worker-0", NS)
        return (pod or {}).get("status", {}).get("log") or ""

    try:
        fake.create(victim)
        spin(grant_is(2), message="victim admitted at full grant")
        # Provably mid-training (first steps logged) before the VIP
        # arrives — early, so plenty of run remains for the live shrink.
        spin(lambda: "step=5 " in victim_log(),
             message="victim mid-training")

        fake.create(_job("vip", replicas=1, priority=10, grace=5,
                         command=["python", "-c",
                                  "print('vip work done')"]))
        spin(grant_is(1), deadline=60,
             message="victim shrunk to 1 host")
        # The victim's loop must absorb the shrink LIVE, well before its
        # run ends.
        spin(lambda: "resharded shrink" in victim_log(), deadline=60,
             message="victim live reshard")
        spin(lambda: _get_job(fake, "vip").get("status", {}).get(
            "state") == "Succeeded", message="vip completion")
        spin(lambda: _get_job(fake, "victim").get("status", {}).get(
            "state") == "Succeeded", message="victim completion")

        victim_job = _get_job(fake, "victim")
        # Shrunk, never killed: no preemption artifacts, no restarts,
        # the one pod lived through the whole run.
        assert victim_job["status"].get("preemptionCount") is None
        assert victim_job["status"].get("restartCount", 0) == 0
        log = fake.get("v1", "Pod", "victim-worker-0",
                       NS)["status"]["log"]
        assert "resumed from checkpoint" not in log
        m = re.search(r"resharded shrink 8->4 devices at step (\d+)",
                      log)
        assert m, f"no live shrink in victim log (seed={seed}):\n" \
                  f"{log[-2000:]}"
        reshard_step = int(m.group(1))
        victim_losses = _losses_from_log(log)
        assert victim_losses.get(steps), "victim never finished"

        # Undisturbed same-global-batch reference: the reshard-point
        # checkpoint restored into the 4-device target mesh, replayed
        # in-process with no scheduler, no chaos, no SIGTERM.
        ref_ck = str(tmp_path / "ref")
        shutil.copytree(ck, ref_ck)
        for entry in os.listdir(ref_ck):
            if entry.isdigit() and int(entry) > reshard_step:
                shutil.rmtree(os.path.join(ref_ck, entry))
        assert ckpt_lib.latest_step(ref_ck) == reshard_step
        lines = []
        ref = run(RunConfig(
            model="lm-test-tiny",
            model_overrides={"n_layers": 2, "d_model": 64, "d_ff": 128},
            steps=steps, log_every=1, batch_size=8, seq_len=32,
            checkpoint_every=10 ** 9, seed=5, checkpoint_dir=ref_ck,
            prefetch=2, graceful_shutdown=False),
            log=lambda *a: lines.append(" ".join(str(x) for x in a)),
            mesh_source=lambda: 4)
        assert ref["step"] == steps
        ref_losses = _losses_from_log("\n".join(lines))
        for step in range(reshard_step + 1, steps + 1):
            assert victim_losses[step] == ref_losses[step], (
                f"seed={seed}: step {step}: victim "
                f"{victim_losses[step]} != reference {ref_losses[step]}")
        # The soak really ran against a hostile apiserver.
        assert len(chaos.faults()) >= 5
    finally:
        kubelet.shutdown()
        httpd.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_chaos_churn_soak_preempt_requeue_resume_data_exact(seed, tmp_path):
    """The acceptance E2E: under seeded apiserver faults plus node
    kills/evictions, gangs are admitted, preempted (real SIGTERM through
    the FakeKubelet grace window), requeued with backoff and resumed —
    every job reaches Succeeded, the VIP preempts within a bounded
    number of reconcile rounds, and the preempted job's final loss is
    byte-equal to an undisturbed reference run."""
    from kubeflow_tpu.train import checkpoint as ckpt_lib

    steps = 120
    fake = FakeApiServer()
    fake.ensure_namespace(NS)
    for crd in jobs_api.all_job_crds():
        fake.apply(crd)
    fake.apply(sched_api.scheduling_policy_crd())
    fake.create(sched_api.scheduling_policy(
        namespace=NS,
        preemption={"requeueBackoffSeconds": 0.5,
                    "gracePeriodSeconds": 60}))
    _add_slice(fake, "v5e", "v5e-0", 1)

    # Controllers talk through a hostile apiserver; the kubelet (the
    # node agent) talks to the backend directly, as a real one would.
    chaos = ChaosApiServer(fake, seed=seed, error_rate=0.05,
                           conflict_rate=0.15,
                           error_after_create_rate=0.05,
                           latency_seconds=0.001)
    kubelet = FakeKubelet(fake, cpu_devices_per_pod=1, timeout=600)
    sched = SchedulerController(
        chaos,
        evict=lambda pod, grace: kubelet.evict(
            pod["metadata"]["name"], pod["metadata"]["namespace"],
            grace_seconds=grace))
    jc = JobController(chaos, "JaxJob")

    def tolerant(fn):
        """Drive one reconcile pass the way the threaded runtime would:
        a transient fault or a lost optimistic write just means the next
        pass retries (the workqueue's job); anything else is a bug."""
        from kubeflow_tpu.k8s.client import ApiError

        try:
            fn()
        except ApiError as e:
            if not e.transient and e.code != 409:
                raise

    def spin(predicate, deadline=300.0, message="condition"):
        end = time.monotonic() + deadline
        rounds = 0
        while time.monotonic() < end:
            kubelet.step()
            tolerant(jc.reconcile_all)
            tolerant(sched.reconcile_all)
            rounds += 1
            if predicate():
                return rounds
            time.sleep(0.05)
        raise AssertionError(f"soak timed out waiting for {message} "
                             f"(seed={seed})")

    try:
        # 1. Undisturbed reference run (unmanaged: no scheduler gate).
        fake.create(_train_job("control", str(tmp_path / "ctl"), steps))
        spin(lambda: fake.get(jobs_api.JOBS_API_VERSION, "JaxJob",
                              "control", NS).get("status", {}).get(
                                  "state") == "Succeeded",
             message="control run")
        control_losses = _losses_from_log(
            fake.get("v1", "Pod", "control-worker-0",
                     NS)["status"]["log"])
        assert control_losses.get(steps), "control never finished"

        # 2. Managed low-priority job admitted onto the single-host
        # slice; wait until it is provably mid-training (checkpoint).
        ck = str(tmp_path / "victim")
        fake.create(_train_job("victim", ck, steps, priority=0))
        spin(lambda: (ckpt_lib.latest_step(ck) or 0) >= 10,
             message="victim mid-training checkpoint")

        # 3. A higher-priority job arrives: the scheduler must preempt
        # the victim within a bounded number of reconcile rounds.
        fake.create(_job("vip", replicas=1, priority=10, grace=5,
                         command=["python", "-c",
                                  "print('vip work done')"]))
        rounds = spin(
            lambda: fake.get(jobs_api.JOBS_API_VERSION, "JaxJob",
                             "victim", NS)["status"].get(
                                 "scheduling", {}).get("state")
            == sched_api.STATE_PREEMPTED
            or fake.get(jobs_api.JOBS_API_VERSION, "JaxJob", "victim",
                        NS)["status"].get("preemptionCount", 0) >= 1,
            deadline=120, message="priority preemption")
        assert rounds <= 20, f"preemption took {rounds} rounds"
        # The SIGTERM grace window produced a checkpoint at the common
        # eviction step (the gang-coordinated save path).
        victim_pod_log = ""
        spin(lambda: fake.get(jobs_api.JOBS_API_VERSION, "JaxJob",
                              "vip", NS).get("status", {}).get(
                                  "state") == "Succeeded",
             message="vip completion")

        # 4. The victim requeues after backoff, resumes from its
        # checkpoint, and completes.
        spin(lambda: fake.get(jobs_api.JOBS_API_VERSION, "JaxJob",
                              "victim", NS).get("status", {}).get(
                                  "state") == "Succeeded",
             message="victim resumed run")
        victim = fake.get(jobs_api.JOBS_API_VERSION, "JaxJob", "victim",
                          NS)
        assert victim["status"].get("preemptionCount", 0) >= 1
        assert victim["status"].get("restartCount", 0) == 0
        victim_pod_log = fake.get("v1", "Pod", "victim-worker-0",
                                  NS)["status"]["log"]
        assert "resumed from checkpoint step" in victim_pod_log

        # 5. Node-kill churn on a fresh managed job: the host dies
        # mid-run, the placement is revoked, replacement capacity
        # arrives, and the job resumes to completion — still data-exact.
        ck2 = str(tmp_path / "churn")
        fake.create(_train_job("churn", ck2, steps, priority=1))
        spin(lambda: (ckpt_lib.latest_step(ck2) or 0) >= 10,
             message="churn job mid-training")
        kubelet.evict_node("v5e-0-h0", grace_seconds=60)
        fake.delete("v1", "Node", "v5e-0-h0")
        spin(lambda: sched_api.placement(fake.get(
            jobs_api.JOBS_API_VERSION, "JaxJob", "churn", NS)) is None,
            deadline=60, message="node-loss revocation")
        _add_slice(fake, "v5e", "v5e-1", 1)
        spin(lambda: fake.get(jobs_api.JOBS_API_VERSION, "JaxJob",
                              "churn", NS).get("status", {}).get(
                                  "state") == "Succeeded",
             message="churn job completion after node replacement")

        # Every job reached Succeeded.
        for name in ("control", "victim", "vip", "churn"):
            job = fake.get(jobs_api.JOBS_API_VERSION, "JaxJob", name, NS)
            assert job["status"].get("state") == "Succeeded", (
                name, job["status"])

        # Data-exactness: final losses byte-equal to the reference run
        # (the logged decimal strings match exactly), for BOTH the
        # preempted-and-resumed job and the node-killed one.
        resumed = _losses_from_log(victim_pod_log)
        assert resumed[steps] == control_losses[steps], (
            f"seed={seed}: victim final loss {resumed[steps]} != "
            f"control {control_losses[steps]}")
        for step, loss in resumed.items():
            assert loss == control_losses[step], (
                f"seed={seed}: victim step {step}: {loss} != "
                f"{control_losses[step]}")
        churn_pod = [p for p in fake.list("v1", "Pod", NS)
                     if p["metadata"]["name"].startswith("churn-")][0]
        churn_losses = _losses_from_log(churn_pod["status"]["log"])
        assert churn_losses[steps] == control_losses[steps], (
            f"seed={seed}: churn final loss {churn_losses[steps]} != "
            f"control {control_losses[steps]}")
        # The soak really ran against a hostile apiserver.
        assert len(chaos.faults()) >= 10
    finally:
        kubelet.shutdown()
