"""ExperimentController tests: the self-tuning loop end to end on the
fake apiserver — knob search over a registered scenario, seed-reproducible
trials, preemptible job-mode trials re-run after eviction, median early
stop, per-trial BENCH profiles that ThroughputBook ingests, and the
winner's promotion as a candidate version that the PR-16 RolloutController
walks (and rolls back, with evidence — the reversibility guarantee)."""

from __future__ import annotations

import json
import os

import pytest

from kubeflow_tpu.apis import jobs as jobs_api
from kubeflow_tpu.apis import scheduling as sched_api
from kubeflow_tpu.apis.experiment import (
    experiment,
    experiment_crd,
    validate_knobs,
)
from kubeflow_tpu.apis.inference import (
    inference_service,
    inference_service_crd,
)
from kubeflow_tpu.operators.experiment import (
    LABEL_EXPERIMENT,
    LABEL_TRIAL,
    TRIAL_PRIORITY,
    ExperimentController,
)
from kubeflow_tpu.serving.scenarios import SYNTHETIC_DEFAULTS, run_trial

NS = "kubeflow"


def _experiment(name="exp", **kw):
    kw.setdefault("algorithm", "random")
    kw.setdefault("max_trials", 6)
    kw.setdefault("parallel_trials", 2)
    kw.setdefault("seed", 5)
    return experiment(name, NS, "synthetic-knobs", **kw)


def _setup(api, exp, **ctrl_kw):
    api.apply(experiment_crd())
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    api.create(exp)
    return ExperimentController(api, **ctrl_kw)


def _drive(api, ctrl, name="exp", rounds=20):
    for _ in range(rounds):
        ctrl.reconcile_all()
        got = api.get("kubeflow-tpu.org/v1", "Experiment", name, NS)
        if got["status"].get("state") in ("Succeeded", "Failed"):
            return got
    return got


# ---------------------------------------------------------------------------
# In-process lifecycle
# ---------------------------------------------------------------------------


def test_inprocess_lifecycle_records_baseline_best_and_seeds(api):
    ctrl = _setup(api, _experiment())
    got = _drive(api, ctrl)
    status = got["status"]
    assert status["state"] == "Succeeded"
    assert status["completedTrialCount"] == 6
    trials = status["trials"]

    # Trial 0 is ALWAYS the checked-in scenario defaults, recorded as
    # full assignments (the experiment's verdict is improvement over
    # this baseline, not an absolute number).
    assert trials[0]["index"] == 0
    assert trials[0]["assignments"] == SYNTHETIC_DEFAULTS
    assert status["baselineObjectiveValue"] == trials[0]["objectiveValue"]

    # Best/improvement verdict is recorded in status.
    best = max(trials, key=lambda t: t["objectiveValue"])
    assert status["bestObjectiveValue"] == best["objectiveValue"]
    assert status["bestTrialIndex"] == best["index"]
    assert status["bestAssignments"] == best["assignments"]
    assert "improvementPercent" in status

    # The ONE experiment seed threads through everything: it is echoed
    # in status and each trial's derived seed is recorded so a re-run
    # observes the same trace.
    assert status["seed"] == 5
    for t in trials:
        assert t["seed"] == 5 * 100_003 + t["index"]
        assert t["state"] == "Succeeded"
        assert "tokens_per_sec" in t["objectives"]


def test_same_seed_reproduces_trials_exactly(api):
    from kubeflow_tpu.k8s.fake import FakeApiServer

    def run(seed):
        srv = FakeApiServer()
        srv.ensure_namespace(NS)
        ctrl = _setup(srv, _experiment(seed=seed))
        got = _drive(srv, ctrl)
        return [(t["assignments"], t["objectiveValue"], t["seed"])
                for t in got["status"]["trials"]]

    assert run(11) == run(11)
    # A different experiment seed proposes a different trajectory.
    a, b = run(11), run(12)
    assert [x[0] for x in a[1:]] != [x[0] for x in b[1:]]


def test_unknown_scenario_fails_experiment(api):
    exp = _experiment()
    exp["spec"]["scenario"] = "no-such-scenario"
    ctrl = _setup(api, exp)
    got = _drive(api, ctrl, rounds=1)
    assert got["status"]["state"] == "Failed"
    assert "no-such-scenario" in got["status"]["reason"]


def test_goal_stops_before_max_trials(api):
    # The synthetic ridge tops out near 100; a trivially met goal stops
    # the search after the first reconcile batch.
    ctrl = _setup(api, _experiment(goal=1.0, max_trials=10))
    got = _drive(api, ctrl)
    assert got["status"]["state"] == "Succeeded"
    assert got["status"]["completedTrialCount"] < 10


# ---------------------------------------------------------------------------
# Profiles: tuner measurements become scheduler capacity knowledge
# ---------------------------------------------------------------------------


def test_trial_profiles_feed_throughput_book(api, tmp_path):
    from kubeflow_tpu.scheduler.capacity import ThroughputBook

    ctrl = _setup(api, _experiment(max_trials=3),
                  profile_dir=str(tmp_path))
    got = _drive(api, ctrl)
    paths = [t["profilePath"] for t in got["status"]["trials"]]
    assert len(paths) == 3 and all(os.path.exists(p) for p in paths)
    rec = json.load(open(paths[0]))
    assert "parsed" in rec and "config" in rec["parsed"]

    book = ThroughputBook.from_bench_files(
        {f"v5e-{i}": p for i, p in enumerate(paths)})
    # Profile name = first token of the trial's config line.
    profile = rec["parsed"]["config"].split()[0]
    assert profile == "synthetic-knobs"
    assert book.throughput(profile, "v5e-0") == \
        rec["parsed"]["tokens_per_sec_per_chip"]


# ---------------------------------------------------------------------------
# Job-mode trials: preemptible background load
# ---------------------------------------------------------------------------


def _finish_job(api, job, value, curve=None):
    job["status"] = {"state": "Succeeded",
                     "metrics": {"tokens_per_sec": value}}
    if curve is not None:
        job["status"]["metricsHistory"] = curve
    api.update_status(job)


def test_job_mode_renders_preemptible_trial_jobs(api):
    ctrl = _setup(api, _experiment(trial_mode="job", parallel_trials=2))
    ctrl.reconcile_all()
    jobs = api.list(jobs_api.JOBS_API_VERSION, "JaxJob", NS)
    assert len(jobs) == 2
    job = next(j for j in jobs
               if j["metadata"]["labels"][LABEL_TRIAL] == "0")
    # Background load: loses every capacity fight.
    assert job["spec"]["priority"] == TRIAL_PRIORITY
    assert job["metadata"]["labels"][LABEL_EXPERIMENT] == "exp"
    assert job["metadata"]["ownerReferences"][0]["kind"] == "Experiment"
    cmd = job["spec"]["replicaSpecs"]["Worker"]["template"]["spec"][
        "containers"][0]["command"]
    # The trial job replays the named scenario with the recorded seed
    # and knob assignments through the scenarios module.
    assert cmd[:3] == ["python", "-m", "kubeflow_tpu.serving.scenarios"]
    assert cmd[cmd.index("--scenario") + 1] == "synthetic-knobs"
    assert cmd[cmd.index("--seed") + 1] == str(5 * 100_003)
    assert json.loads(cmd[cmd.index("--assignments") + 1]) \
        == SYNTHETIC_DEFAULTS


def test_decode_tps_trial_drives_the_live_engine_and_drains():
    """The one scenario that boots a ContinuousDecoder. A CPU shows its
    counts: the block size legalized to a divisor of the row, the pool
    held at sixteen rows whatever the knobs, every block back after the
    drain, and the line the ThroughputBook reads."""
    res = run_trial("decode-tps", {"slots": 8, "kv_block_size": 10},
                    seed=3, quick=True)
    obj = res["objectives"]
    # 48-token rows: 10 snaps down to 8, and 16 rows are 96 blocks of 8.
    assert res["config"] == \
        "decode-tps slots8 block8 buckets0 pool96 n24 seed3"
    assert obj["kv_blocks_in_use_after_drain"] == 0
    assert obj["kv_bytes_peak"] > 0
    assert res["tokens_per_sec_per_chip"] == obj["tokens_per_sec"] > 0


def test_preempted_trial_reruns_same_assignments_and_seed(api):
    ctrl = _setup(api, _experiment(trial_mode="job", parallel_trials=1,
                                   max_trials=2))
    ctrl.reconcile_all()
    job = api.list(jobs_api.JOBS_API_VERSION, "JaxJob", NS)[0]
    name0 = job["metadata"]["name"]
    # The scheduler evicts the trial for real work.
    job["metadata"].setdefault("annotations", {})[
        sched_api.ANN_PREEMPTED_BY] = "prod-job"
    api.update(job)
    ctrl.reconcile_all()

    jobs = api.list(jobs_api.JOBS_API_VERSION, "JaxJob", NS)
    assert len(jobs) == 1
    rerun = jobs[0]
    # Fresh job object (retry suffix), same trial identity: the poisoned
    # measurement window is discarded, the trace replays byte-for-byte.
    assert rerun["metadata"]["name"] == f"{name0}-r1"
    cmd0_seed = str(5 * 100_003)
    cmd = rerun["spec"]["replicaSpecs"]["Worker"]["template"]["spec"][
        "containers"][0]["command"]
    assert cmd[cmd.index("--seed") + 1] == cmd0_seed
    got = api.get("kubeflow-tpu.org/v1", "Experiment", "exp", NS)
    trial = got["status"]["trials"][0]
    assert trial["retries"] == 1 and trial["state"] == "Running"

    # The re-run completes and counts once.
    _finish_job(api, rerun, 50.0)
    ctrl.reconcile_all()
    got = api.get("kubeflow-tpu.org/v1", "Experiment", "exp", NS)
    assert got["status"]["trials"][0]["state"] == "Succeeded"
    assert got["status"]["trials"][0]["objectiveValue"] == 50.0


def test_job_mode_median_early_stop(api):
    ctrl = _setup(api, _experiment(
        trial_mode="job", parallel_trials=4, max_trials=4,
        early_stop={"policy": "median", "minTrials": 3}))
    ctrl.reconcile_all()
    jobs = sorted(api.list(jobs_api.JOBS_API_VERSION, "JaxJob", NS),
                  key=lambda j: int(j["metadata"]["labels"][LABEL_TRIAL]))
    assert len(jobs) == 4
    # Three trials complete with healthy curves; the fourth is mid-run
    # and clearly below the median at the same step.
    for job, final in zip(jobs[:3], (80.0, 90.0, 100.0)):
        _finish_job(api, job, final,
                    curve=[[1, final / 2], [2, final]])
    laggard = jobs[3]
    laggard["status"] = {"state": "Running",
                         "metricsHistory": [[1, 5.0], [2, 10.0]]}
    api.update_status(laggard)
    # First pass collects the three finished curves into status; the
    # median gate judges the laggard against them on the next pass.
    ctrl.reconcile_all()
    ctrl.reconcile_all()

    got = api.get("kubeflow-tpu.org/v1", "Experiment", "exp", NS)
    trial = got["status"]["trials"][3]
    # Early stop is an observation, not a failure: the partial
    # measurement IS the trial's objective.
    assert trial["state"] == "Succeeded"
    assert trial["earlyStopped"] is True
    assert trial["objectiveValue"] == 10.0
    assert api.get_or_none(jobs_api.JOBS_API_VERSION, "JaxJob",
                           laggard["metadata"]["name"], NS) is None
    got = _drive(api, ctrl)
    assert got["status"]["state"] == "Succeeded"


# ---------------------------------------------------------------------------
# Promotion: recorded, and reversible through the rollout controller
# ---------------------------------------------------------------------------


def _target_cr(name="llm"):
    return inference_service(
        name, NS, "lm-test-tiny", replicas=4, max_replicas=4,
        rollout={"stepSeconds": 1.0, "shadowSeconds": 1.0},
        autoscale={"scrapePeriodSeconds": 5,
                   "signalStalenessSeconds": 20})


def test_promotion_writes_candidate_version_with_engine(api):
    api.apply(inference_service_crd())
    api.create(_target_cr())
    ctrl = _setup(api, _experiment(
        promotion={"target": "llm", "minImprovementPercent": 0.0}))
    got = _drive(api, ctrl)
    promo = got["status"]["promotion"]
    assert promo["target"] == "llm"
    assert promo["version"] == "exp-tuned"
    assert promo["engine"] == got["status"]["bestAssignments"]
    assert promo["improvementPercent"] == \
        got["status"]["improvementPercent"]

    svc = api.get("kubeflow-tpu.org/v1", "InferenceService", "llm", NS)
    incumbent, candidate = svc["spec"]["versions"]
    # Incumbent keeps serving (traffic flows through status.rollout as
    # the walk progresses); the candidate carries the knob overrides.
    assert incumbent["traffic"] == 0.0
    assert candidate["name"] == "exp-tuned"
    assert candidate["traffic"] == 100.0
    assert candidate["engine"] == promo["engine"]
    assert candidate["weightsRef"] == incumbent["weightsRef"]


def test_promotion_skipped_below_min_improvement(api):
    api.apply(inference_service_crd())
    api.create(_target_cr())
    ctrl = _setup(api, _experiment(
        promotion={"target": "llm", "minImprovementPercent": 1e9}))
    got = _drive(api, ctrl)
    promo = got["status"]["promotion"]
    assert promo["skipped"] is True and "below minimum" in promo["reason"]
    svc = api.get("kubeflow-tpu.org/v1", "InferenceService", "llm", NS)
    assert "versions" not in svc["spec"]


def test_promoted_winner_is_reversible_through_rollout(api):
    """The acceptance path: a tuned candidate that regresses live SLOs
    is rolled back BY the rollout controller with gate-breach evidence —
    the experiment's promotion is a recorded, reversible rollout step,
    never a blind config overwrite."""
    from test_rollout import CALM, SLOW, StubFleet

    from kubeflow_tpu.operators.rollout import RolloutController

    api.apply(inference_service_crd())
    api.create(_target_cr())
    ctrl = _setup(api, _experiment(
        promotion={"target": "llm", "minImprovementPercent": 0.0}))
    got = _drive(api, ctrl)
    assert got["status"]["promotion"]["version"] == "exp-tuned"

    clock = {"t": 0.0}
    fleet = StubFleet([f"llm-r{i}" for i in range(4)])
    sig = {"by_addr": {}}

    def fetch(addr):
        v = sig["by_addr"].get(addr, CALM)
        return dict(v) if v is not None else None

    rc = RolloutController(api, fleet_for=lambda ns, n: fleet,
                           weights_for=lambda ref: "W-TUNED",
                           fetch_metrics=fetch,
                           clock=lambda: clock["t"])
    rc.reconcile_all()
    ro = api.get("kubeflow-tpu.org/v1", "InferenceService", "llm",
                 NS)["status"]["rollout"]
    assert ro["phase"] == "Shadow"
    assert ro["canaryMembers"] == ["llm-r3"]

    # The tuned knobs regress TTFT on the canary cohort: the gate
    # breaches and the controller rolls the fleet back with evidence.
    sig["by_addr"][f"llm-r3.{NS}:8500"] = dict(SLOW)
    clock["t"] += 2.0
    rc.reconcile_all()
    ro = api.get("kubeflow-tpu.org/v1", "InferenceService", "llm",
                 NS)["status"]["rollout"]
    assert ro["phase"] == "RolledBack"
    assert ro["evidence"]["reason"] == "gate-breach"
    assert ro["evidence"]["signal"] == "ttftP99"
    # The fleet converged back on one (fresh) epoch — reversal is a
    # push, not a hole.
    assert len(set(fleet.installed.values())) == 1


# ---------------------------------------------------------------------------
# Search economy (the ISSUE acceptance gate, judged on the synthetic
# landscape where wall-clock jitter cannot flake it)
# ---------------------------------------------------------------------------


def test_bayesian_reaches_randoms_best_in_half_the_trials():
    from kubeflow_tpu.tuning.sweep import run_policy, trials_to_reach

    trials = 12
    random_best = run_policy("synthetic-knobs", "random", trials, 7,
                             False)["bestObjectiveValue"]
    trace = run_policy("synthetic-knobs", "bayesianoptimization",
                       trials, 7, False)["bestSoFarTrace"]
    n = trials_to_reach(trace, float(random_best))
    assert n is not None and n <= trials // 2


# ---------------------------------------------------------------------------
# API validation
# ---------------------------------------------------------------------------


def test_experiment_builder_validates():
    with pytest.raises(ValueError, match="unknown algorithm"):
        experiment("e", NS, "decode-tps", algorithm="sa")
    with pytest.raises(ValueError, match="objective metric"):
        experiment("e", NS, "decode-tps", objective_metric="latency")
    with pytest.raises(ValueError, match="trial mode"):
        experiment("e", NS, "decode-tps", trial_mode="pod")


def test_validate_knobs_enforces_safe_ranges():
    with pytest.raises(ValueError, match="safe range"):
        validate_knobs([{"name": "slots", "parameterType": "int",
                         "feasibleSpace": {"min": 1, "max": 512}}])
    # Uncataloged knobs pass through (scenarios may declare their own).
    out = validate_knobs([{"name": "custom", "parameterType": "int",
                           "feasibleSpace": {"min": 0, "max": 1}}])
    assert out[0]["name"] == "custom"
