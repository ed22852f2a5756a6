"""Controller tests against the fake apiserver (the envtest tier,
SURVEY.md §4). Pod phase transitions are simulated the way envtest does —
by writing pod status directly."""

import json
import os

import pytest

from kubeflow_tpu.apis import jobs as jobs_api
from kubeflow_tpu.apis.notebooks import notebook, notebook_crd
from kubeflow_tpu.apis.profiles import profile, profile_crd
from kubeflow_tpu.operators.jobs import JobController
from kubeflow_tpu.operators.notebooks import NotebookController
from kubeflow_tpu.operators.profiles import ProfileController


def make_job(kind="JaxJob", name="train", replicas=4, **spec_extra):
    replica_types = {
        "JaxJob": {"Worker": replicas},
        "TFJob": {"Chief": 1, "PS": 2, "Worker": replicas},
        "PyTorchJob": {"Master": 1, "Worker": replicas},
        "MXNetJob": {"Scheduler": 1, "Server": 1, "Worker": replicas},
        "ChainerJob": {"Master": 1, "Worker": replicas},
        "MPIJob": {"Launcher": 1, "Worker": replicas},
    }[kind]
    return {
        "apiVersion": jobs_api.JOBS_API_VERSION,
        "kind": kind,
        "metadata": {"name": name, "namespace": "kubeflow"},
        "spec": {
            "replicaSpecs": {
                rt: {
                    "replicas": n,
                    "restartPolicy": "OnFailure",
                    "template": {"spec": {"containers": [
                        {"name": "main", "image": "train:latest"}
                    ]}},
                }
                for rt, n in replica_types.items()
            },
            **spec_extra,
        },
    }


def set_pod_phase(api, pod_name, phase, exit_code=None):
    pod = api.get("v1", "Pod", pod_name, "kubeflow")
    status = {"phase": phase}
    if exit_code is not None:
        status["containerStatuses"] = [
            {"name": "main", "state": {"terminated": {"exitCode": exit_code}}}
        ]
    pod["status"] = status
    api.update_status(pod)


@pytest.fixture()
def jaxjob_env(api):
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "JaxJob")
    return api, ctrl


def test_jaxjob_creates_gang_and_env(jaxjob_env):
    api, ctrl = jaxjob_env
    api.create(make_job(tpu={"accelerator": "v5e", "topology": "2x4"}))
    ctrl.reconcile_all()

    pods = api.list("v1", "Pod", "kubeflow")
    assert len(pods) == 4
    svc = api.get("v1", "Service", "train", "kubeflow")
    assert svc["spec"]["clusterIP"] == "None"

    pod0 = api.get("v1", "Pod", "train-worker-0", "kubeflow")
    env = {e["name"]: e["value"] for e in pod0["spec"]["containers"][0]["env"]}
    assert env["JAX_COORDINATOR_ADDRESS"] == (
        "train-worker-0.train.kubeflow:8476"
    )
    assert env["JAX_NUM_PROCESSES"] == "4"
    assert env["JAX_PROCESS_ID"] == "0"
    assert pod0["spec"]["nodeSelector"][
        "cloud.google.com/gke-tpu-accelerator"] == "v5e"
    assert pod0["spec"]["subdomain"] == "train"

    job = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert job["status"]["state"] == "Created"
    assert job["status"]["replicaStatuses"]["worker"]["pending"] == 4


def test_jaxjob_running_then_succeeded_cleans_pods(jaxjob_env):
    api, ctrl = jaxjob_env
    api.create(make_job(replicas=2))
    ctrl.reconcile_all()
    for i in range(2):
        set_pod_phase(api, f"train-worker-{i}", "Running")
    ctrl.reconcile_all()
    job = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert job["status"]["state"] == "Running"

    for i in range(2):
        set_pod_phase(api, f"train-worker-{i}", "Succeeded")
    ctrl.reconcile_all()
    job = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert job["status"]["state"] == "Succeeded"
    conds = {c["type"]: c["status"] for c in job["status"]["conditions"]}
    assert conds["Succeeded"] == "True"
    # cleanPodPolicy default Running: succeeded pods stay.
    assert len(api.list("v1", "Pod", "kubeflow")) == 2


def test_jaxjob_restart_on_failure_and_backoff(jaxjob_env):
    api, ctrl = jaxjob_env
    api.create(make_job(replicas=2, runPolicy={"backoffLimit": 1}))
    ctrl.reconcile_all()
    set_pod_phase(api, "train-worker-0", "Failed")
    ctrl.reconcile_all()
    job = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert job["status"]["restartCount"] == 1
    assert job["status"]["state"] == "Restarting"
    # Pod was recreated fresh (Pending).
    pod = api.get("v1", "Pod", "train-worker-0", "kubeflow")
    assert pod.get("status", {}).get("phase") is None

    # Second failure exceeds backoffLimit=1.
    set_pod_phase(api, "train-worker-0", "Failed")
    ctrl.reconcile_all()
    job = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert job["status"]["state"] == "Failed"
    reasons = [c["reason"] for c in job["status"]["conditions"]
               if c["status"] == "True"]
    assert "BackoffLimitExceeded" in reasons


def test_jaxjob_never_restart_fails_job(jaxjob_env):
    api, ctrl = jaxjob_env
    job = make_job(replicas=2)
    for rs in job["spec"]["replicaSpecs"].values():
        rs["restartPolicy"] = "Never"
    api.create(job)
    ctrl.reconcile_all()
    set_pod_phase(api, "train-worker-1", "Failed")
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"]["state"] == "Failed"


def test_jaxjob_exitcode_policy(jaxjob_env):
    api, ctrl = jaxjob_env
    job = make_job(replicas=1)
    job["spec"]["replicaSpecs"]["Worker"]["restartPolicy"] = "ExitCode"
    api.create(job)
    ctrl.reconcile_all()
    # Exit 1 = permanent failure.
    set_pod_phase(api, "train-worker-0", "Failed", exit_code=1)
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"]["state"] == "Failed"


def test_jaxjob_exitcode_sigkill_restarts(jaxjob_env):
    api, ctrl = jaxjob_env
    job = make_job(replicas=1)
    job["spec"]["replicaSpecs"]["Worker"]["restartPolicy"] = "ExitCode"
    api.create(job)
    ctrl.reconcile_all()
    set_pod_phase(api, "train-worker-0", "Failed", exit_code=137)
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"]["state"] == "Restarting"


def test_jaxjob_invalid_spec_fails(jaxjob_env):
    api, ctrl = jaxjob_env
    bad = make_job()
    bad["spec"]["replicaSpecs"]["Worker"]["template"] = {"spec": {}}
    api.create(bad)
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"]["state"] == "Failed"
    assert any(c["reason"] == "InvalidSpec"
               for c in got["status"]["conditions"])


def test_jaxjob_multislice_env(jaxjob_env):
    api, ctrl = jaxjob_env
    api.create(make_job(replicas=4, tpu={"accelerator": "v5e",
                                         "numSlices": 2}))
    ctrl.reconcile_all()
    pod3 = api.get("v1", "Pod", "train-worker-3", "kubeflow")
    env = {e["name"]: e["value"] for e in pod3["spec"]["containers"][0]["env"]}
    assert env["MEGASCALE_NUM_SLICES"] == "2"
    assert env["MEGASCALE_SLICE_ID"] == "1"
    assert env["TPU_WORKER_ID"] == "1"


def test_tfjob_tf_config(api):
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "TFJob")
    api.create(make_job("TFJob", replicas=2))
    ctrl.reconcile_all()
    pod = api.get("v1", "Pod", "train-worker-1", "kubeflow")
    env = {e["name"]: e["value"] for e in pod["spec"]["containers"][0]["env"]}
    tf_config = json.loads(env["TF_CONFIG"])
    assert tf_config["task"] == {"type": "worker", "index": 1}
    assert len(tf_config["cluster"]["ps"]) == 2
    assert tf_config["cluster"]["chief"][0].endswith(":8476")
    # Chief completion defines success.
    set_pod_phase(api, "train-chief-0", "Succeeded")
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "TFJob", "train", "kubeflow")
    assert got["status"]["state"] == "Succeeded"


def test_pytorchjob_master_env(api):
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "PyTorchJob")
    api.create(make_job("PyTorchJob", replicas=3))
    ctrl.reconcile_all()
    pod = api.get("v1", "Pod", "train-worker-2", "kubeflow")
    env = {e["name"]: e["value"] for e in pod["spec"]["containers"][0]["env"]}
    assert env["MASTER_ADDR"] == "train-master-0.train.kubeflow"
    assert env["WORLD_SIZE"] == "4"
    assert env["RANK"] == "3"


def test_mpijob_hostfile(api):
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "MPIJob")
    api.create(make_job("MPIJob", replicas=2))
    ctrl.reconcile_all()
    pod = api.get("v1", "Pod", "train-launcher-0", "kubeflow")
    env = {e["name"]: e["value"] for e in pod["spec"]["containers"][0]["env"]}
    assert "train-worker-0.train.kubeflow slots=1" in env["MPI_HOSTFILE_CONTENT"]


def test_notebook_controller_creates_statefulset_and_status(api):
    api.apply(notebook_crd())
    ctrl = NotebookController(api)
    api.create(notebook("nb1", "kubeflow", "jax-notebook:latest",
                        tpu_chips=4, workspace_pvc="ws"))
    ctrl.reconcile_all()
    sts = api.get("apps/v1", "StatefulSet", "nb1", "kubeflow")
    assert sts["spec"]["replicas"] == 1
    main = sts["spec"]["template"]["spec"]["containers"][0]
    assert main["resources"]["limits"]["google.com/tpu"] == 4
    assert api.get("v1", "Service", "nb1", "kubeflow")

    # Simulate the pod coming up; status mirrors container state.
    pod_tmpl = sts["spec"]["template"]
    pod = {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": "nb1-0", "namespace": "kubeflow",
                     "labels": pod_tmpl["metadata"]["labels"]},
        "spec": pod_tmpl["spec"],
    }
    api.create(pod)
    set_pod_phase(api, "nb1-0", "Running")
    ctrl.reconcile_all()
    nb = api.get("kubeflow-tpu.org/v1", "Notebook", "nb1", "kubeflow")
    assert nb["status"]["readyReplicas"] == 1


def test_notebook_suspend_scales_statefulset(api):
    api.apply(notebook_crd())
    ctrl = NotebookController(api)
    api.create(notebook("nb2", "kubeflow", "jax-notebook:latest"))
    ctrl.reconcile_all()
    assert api.get("apps/v1", "StatefulSet", "nb2", "kubeflow")["spec"][
        "replicas"] == 1
    nb = api.get("kubeflow-tpu.org/v1", "Notebook", "nb2", "kubeflow")
    nb["spec"]["suspend"] = True
    api.update(nb)
    ctrl.reconcile_all()
    assert api.get("apps/v1", "StatefulSet", "nb2", "kubeflow")["spec"][
        "replicas"] == 0


def test_profile_controller_provisions_namespace_rbac_quota(api):
    api.apply(profile_crd())
    ctrl = ProfileController(api)
    api.create(profile("alice", "alice@example.com",
                       quota={"hard": {"requests.google.com/tpu": "8"}}))
    ctrl.reconcile_all()
    assert api.get("v1", "Namespace", "alice")
    role = api.get("rbac.authorization.k8s.io/v1", "Role",
                   "namespace-admin", "alice")
    assert role["rules"][0]["verbs"] == ["*"]
    binding = api.get("rbac.authorization.k8s.io/v1", "RoleBinding",
                      "namespace-admin-binding", "alice")
    assert binding["subjects"][0]["name"] == "alice@example.com"
    quota = api.get("v1", "ResourceQuota", "profile-quota", "alice")
    assert quota["spec"]["hard"]["requests.google.com/tpu"] == "8"
    prof = api.get("kubeflow-tpu.org/v1", "Profile", "alice")
    assert prof["status"]["state"] == "Ready"


def test_jaxjob_gang_restart_restarts_all_workers(jaxjob_env):
    api, ctrl = jaxjob_env
    api.create(make_job(replicas=3))
    ctrl.reconcile_all()
    for i in range(3):
        set_pod_phase(api, f"train-worker-{i}", "Running")
    ctrl.reconcile_all()
    # One worker fails retryably: surviving peers hold a dead rendezvous, so
    # the WHOLE gang must be recreated.
    set_pod_phase(api, "train-worker-1", "Failed")
    ctrl.reconcile_all()
    job = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert job["status"]["restartCount"] == 1
    for i in range(3):
        pod = api.get("v1", "Pod", f"train-worker-{i}", "kubeflow")
        assert pod.get("status", {}).get("phase") is None, i
    reasons = [c["reason"] for c in job["status"]["conditions"]
               if c["status"] == "True"]
    assert "GangRestarting" in reasons


def test_jaxjob_gang_restart_does_not_mask_permanent_failure(jaxjob_env):
    api, ctrl = jaxjob_env
    job = make_job(replicas=2)
    for rs in job["spec"]["replicaSpecs"].values():
        rs["restartPolicy"] = "ExitCode"
    api.create(job)
    ctrl.reconcile_all()
    # worker-0 permanent (exit 1), worker-1 retryable (SIGKILL 137): the job
    # must fail, not gang-restart forever.
    set_pod_phase(api, "train-worker-0", "Failed", exit_code=1)
    set_pod_phase(api, "train-worker-1", "Failed", exit_code=137)
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"]["state"] == "Failed"
    reasons = [c["reason"] for c in got["status"]["conditions"]
               if c["status"] == "True"]
    assert "ReplicaFailed" in reasons


def test_jaxjob_declined_gang_restart_does_not_churn(jaxjob_env):
    api, ctrl = jaxjob_env
    job = make_job(replicas=2)
    for rs in job["spec"]["replicaSpecs"].values():
        rs["restartPolicy"] = "ExitCode"
    job["spec"]["runPolicy"] = {"backoffLimit": 0}
    api.create(job)
    ctrl.reconcile_all()
    set_pod_phase(api, "train-worker-0", "Failed", exit_code=1)    # permanent
    set_pod_phase(api, "train-worker-1", "Failed", exit_code=137)  # retryable
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"]["state"] == "Failed"
    reasons = [c["reason"] for c in got["status"]["conditions"]
               if c["status"] == "True"]
    # Declined gang restart: no solo pod churn, no spurious restartCount, so
    # the failure reason is ReplicaFailed (not BackoffLimitExceeded).
    assert "ReplicaFailed" in reasons
    assert got["status"].get("restartCount", 0) == 0


def test_mpi_launcher_hostfile_wait_and_command(tmp_path):
    """MPIJob launcher contract: hostfile written from the controller-shipped
    env, workers waited on, mpirun line assembled (kubectl-delivery +
    mpi-operator launcher semantics)."""
    from kubeflow_tpu.workloads.mpi_launcher import (
        build_command,
        parse_hostfile,
        wait_for_workers,
        write_hostfile,
    )

    content = "w0.job.ns slots=4\nw1.job.ns slots=4\n# comment\n"
    path = str(tmp_path / "etc" / "hostfile")
    entries = write_hostfile(content, path)
    assert entries == [("w0.job.ns", 4), ("w1.job.ns", 4)]
    assert parse_hostfile(open(path).read()) == entries

    resolved = {"w0.job.ns"}
    calls = []

    def resolve(host):
        calls.append(host)
        if host not in resolved:
            resolved.add(host)  # appears on the second poll
            raise OSError("not yet")
        return "10.0.0.1"

    wait_for_workers([h for h, _ in entries], timeout=10, poll=0.01,
                     resolve=resolve, log=lambda *a: None)
    assert calls.count("w1.job.ns") == 2  # actually polled until resolvable

    cmd = build_command(["python", "train.py"], path, entries,
                        mpirun="/usr/bin/mpirun")
    assert cmd[:5] == ["/usr/bin/mpirun", "--hostfile", path, "-np", "8"]
    assert cmd[-2:] == ["python", "train.py"]
    # No mpirun / no workers -> run the command directly.
    assert build_command(["python", "train.py"], path, [], mpirun=None) == [
        "python", "train.py"
    ]


def test_mpi_launcher_main_single_process(tmp_path, monkeypatch):
    """End to end in single-process mode: writes the hostfile and execs the
    wrapped command (no MPI runtime in the test image)."""
    import kubeflow_tpu.workloads.mpi_launcher as ml

    hostfile = str(tmp_path / "hostfile")
    monkeypatch.setenv(ml.ENV_HOSTFILE_CONTENT, "")
    monkeypatch.setattr(ml.shutil, "which", lambda _: None)
    ran = {}
    monkeypatch.setattr(ml.subprocess, "call",
                        lambda cmd: ran.setdefault("cmd", cmd) and 0 or 0)
    rc = ml.main(["--hostfile", hostfile, "--", "echo", "ok"])
    assert rc == 0
    assert ran["cmd"] == ["echo", "ok"]


def test_jaxjob_preemption_reschedules_without_burning_backoff(jaxjob_env):
    """Preemption (node reclaim) gang-reschedules under ANY restart policy
    and never counts against backoffLimit (SURVEY §5.3 elastic semantics)."""
    api, ctrl = jaxjob_env
    job = make_job(replicas=2, runPolicy={"backoffLimit": 0})
    job["spec"]["replicaSpecs"]["Worker"]["restartPolicy"] = "Never"
    api.create(job)
    ctrl.reconcile_all()
    pods = api.list("v1", "Pod", "kubeflow")
    assert len(pods) == 2

    # Node reclaimed: kubelet marks the pod Failed reason=Preempted.
    victim = pods[0]["metadata"]["name"]
    pod = api.get("v1", "Pod", victim, "kubeflow")
    pod["status"] = {"phase": "Failed", "reason": "Preempted",
                     "containerStatuses": [{"name": "main", "state": {
                         "terminated": {"exitCode": 137}}}]}
    api.update_status(pod)

    ctrl.reconcile_all()  # gang deleted
    ctrl.reconcile_all()  # gang recreated
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"].get("preemptionCount", 0) == 1
    assert got["status"].get("restartCount", 0) == 0
    assert got["status"]["state"] != "Failed"  # backoffLimit=0 untouched
    conds = {c["type"]: c["reason"] for c in got["status"]["conditions"]}
    assert conds.get("Restarting") == "GangPreempted"
    assert len(api.list("v1", "Pod", "kubeflow")) == 2  # rescheduled


def test_preemption_recognized_by_disruption_target_condition(jaxjob_env):
    """Regression: a Failed pod carrying ONLY the DisruptionTarget
    condition (no kubelet reason string) still counts as preemption —
    preemptionCount bumps, backoffLimit untouched."""
    api, ctrl = jaxjob_env
    api.create(make_job(replicas=2, runPolicy={"backoffLimit": 0}))
    ctrl.reconcile_all()
    pod = api.get("v1", "Pod", "train-worker-0", "kubeflow")
    pod["status"] = {"phase": "Failed",
                     "conditions": [{"type": "DisruptionTarget",
                                     "status": "True",
                                     "reason": "EvictionByEvictionAPI"}]}
    api.update_status(pod)
    ctrl.reconcile_all()
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"].get("preemptionCount", 0) == 1
    assert got["status"].get("restartCount", 0) == 0
    assert got["status"]["state"] != "Failed"


def test_preemption_recognized_by_scheduler_annotation(jaxjob_env):
    """Regression: a Failed pod whose ONLY preemption signal is the
    scheduler-set kubeflow-tpu.org/preempted-by annotation (no reason,
    no condition) is accounted as a preemption, not a workload failure —
    the contract for scheduler-initiated evictions."""
    from kubeflow_tpu.apis import scheduling as sched_api

    api, ctrl = jaxjob_env
    api.create(make_job(replicas=2, runPolicy={"backoffLimit": 0}))
    ctrl.reconcile_all()
    pod = api.get("v1", "Pod", "train-worker-0", "kubeflow")
    pod["metadata"].setdefault("annotations", {})[
        sched_api.ANN_PREEMPTED_BY] = "JaxJob/kubeflow/vip"
    api.update(pod)
    pod = api.get("v1", "Pod", "train-worker-0", "kubeflow")
    pod["status"] = {"phase": "Failed"}  # no reason, no conditions
    api.update_status(pod)
    ctrl.reconcile_all()
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"].get("preemptionCount", 0) == 1
    assert got["status"].get("restartCount", 0) == 0
    assert got["status"]["state"] != "Failed"  # backoffLimit=0 untouched


def test_jaxjob_unknown_phase_counts_as_gang_failure(jaxjob_env):
    """A pod stuck in Unknown (node unreachable) triggers the gang restart
    path instead of hanging the collective."""
    api, ctrl = jaxjob_env
    api.create(make_job(replicas=2))
    ctrl.reconcile_all()
    name = api.list("v1", "Pod", "kubeflow")[0]["metadata"]["name"]
    pod = api.get("v1", "Pod", name, "kubeflow")
    pod["status"] = {"phase": "Unknown",
                     "conditions": [{"type": "DisruptionTarget",
                                     "status": "True"}]}
    api.update_status(pod)
    ctrl.reconcile_all()
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    assert got["status"].get("preemptionCount", 0) == 1
    assert len(api.list("v1", "Pod", "kubeflow")) == 2


def test_slice_health_probe_runs():
    """The health probe passes on the virtual slice and fails on an
    impossible expectation."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    ok = subprocess.run(
        [sys.executable, "-m", "kubeflow_tpu.workloads.slice_health",
         "--expect-local-devices", "2"],
        capture_output=True, text=True, timeout=180, env=env,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    report = json.loads(ok.stdout.strip().splitlines()[-1])
    assert report["healthy"] and report["psum"] == 4.0

    bad = subprocess.run(
        [sys.executable, "-m", "kubeflow_tpu.workloads.slice_health",
         "--expect-devices", "999"],
        capture_output=True, text=True, timeout=180, env=env,
    )
    assert bad.returncode == 1
    assert "999" in json.loads(bad.stdout.strip().splitlines()[-1])["error"]


def test_mpi_sidecar_follows_launcher_phase(api):
    """openmpi-controller semantics (controller.py:92-104): the worker
    sidecar exits with the launcher pod's outcome."""
    from kubeflow_tpu.workloads.mpi_sidecar import wait_for_launcher

    api.create({
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": "job-launcher-0", "namespace": "kubeflow",
                     "labels": {"kubeflow-tpu.org/job-name": "job",
                                "kubeflow-tpu.org/replica-type": "launcher"}},
        "spec": {"containers": [{"name": "l", "image": "i"}]},
        "status": {"phase": "Running"},
    })
    phases = iter(["Running", "Succeeded"])

    def tick(_):
        pod = api.get("v1", "Pod", "job-launcher-0", "kubeflow")
        pod["status"]["phase"] = next(phases)
        api.update_status(pod)

    rc = wait_for_launcher(api, "job", "kubeflow", poll_seconds=0,
                           log=lambda *a: None, sleep=tick)
    assert rc == 0

    pod = api.get("v1", "Pod", "job-launcher-0", "kubeflow")
    pod["status"]["phase"] = "Failed"
    api.update_status(pod)
    assert wait_for_launcher(api, "job", "kubeflow", poll_seconds=0,
                             log=lambda *a: None, sleep=lambda s: None) == 1
    # Launcher gone entirely -> failure after the grace polls.
    api.delete("v1", "Pod", "job-launcher-0", "kubeflow")
    assert wait_for_launcher(api, "job", "kubeflow", poll_seconds=0,
                             grace_polls=1, log=lambda *a: None,
                             sleep=lambda s: None) == 1


def test_leader_election_single_holder_and_failover(api):
    """Lease semantics: one holder at a time; standby takes over when the
    lease expires or is released (client-go leaderelection analogue)."""
    import time as _time

    from kubeflow_tpu.operators.leader import LeaderElector

    a = LeaderElector(api, name="op", identity="a", lease_seconds=1)
    b = LeaderElector(api, name="op", identity="b", lease_seconds=1)
    assert a.try_acquire() is True
    assert b.try_acquire() is False
    assert a.is_leader and not b.is_leader
    # Renewal keeps leadership.
    assert a.try_acquire() is True
    _time.sleep(0.6)
    assert a.try_acquire() is True  # renewal resets b's observation clock
    _time.sleep(0.6)
    assert b.try_acquire() is False  # 1.2s since b's first observation,
    # but only 0.6s since the record last changed — lease still healthy

    # Leader stops renewing → standby takes over after a full local
    # lease duration with no observed transition.
    _time.sleep(1.1)
    assert b.try_acquire() is True
    assert a.try_acquire() is False  # a lost it

    # Clean release: a can immediately re-acquire.
    b.release()
    assert a.try_acquire() is True


def test_leader_election_tolerates_clock_skew(api):
    """A leader on a node whose clock is minutes behind writes renewTimes
    that look expired against the local wall clock, but it renews on
    schedule — a standby must judge expiry from locally observed renewTime
    *transitions* (monotonic), never wall-clock comparison, so a healthy
    skewed leader is never seized from."""
    import datetime
    import time as _time

    from kubeflow_tpu.operators.leader import (
        LEASE_API_VERSION,
        LeaderElector,
    )

    def skewed_stamp(seconds_ago):
        return (datetime.datetime.now(datetime.timezone.utc)
                - datetime.timedelta(seconds=seconds_ago)).strftime(
                    "%Y-%m-%dT%H:%M:%S.%fZ")

    api.create({
        "apiVersion": LEASE_API_VERSION, "kind": "Lease",
        "metadata": {"name": "skew", "namespace": "kubeflow"},
        "spec": {"holderIdentity": "remote-leader",
                 "leaseDurationSeconds": 0.3,
                 "renewTime": skewed_stamp(600)},
    })
    b = LeaderElector(api, name="skew", identity="b", lease_seconds=0.3)
    # First observation starts the local clock; stamp looks 600s stale but
    # that alone must not grant the lease.
    assert b.try_acquire() is False
    # The skewed leader keeps renewing (stamp advances, still "stale").
    for seconds_ago in (599, 598):
        _time.sleep(0.2)
        lease = api.get(LEASE_API_VERSION, "Lease", "skew", "kubeflow")
        lease["spec"]["renewTime"] = skewed_stamp(seconds_ago)
        api.update(lease)
        assert b.try_acquire() is False  # record changed → leader healthy
    # Renewals stop → after a locally-observed full lease duration b leads.
    _time.sleep(0.4)
    assert b.try_acquire() is True


@pytest.mark.slow
def test_leader_elected_manager_exits_on_leadership_loss(api):
    """Split-brain guard end to end: a real manager process acquires the
    Lease over HTTP, then exits nonzero when another identity steals it
    (client-go OnStoppedLeading-is-fatal semantics)."""
    import subprocess
    import sys
    import time

    from kubeflow_tpu.apis.profiles import profile_crd
    from kubeflow_tpu.k8s.httpfake import serve

    api.apply(profile_crd())
    httpd, port = serve(api)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               KUBEFLOW_TPU_APISERVER=f"http://127.0.0.1:{port}")
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubeflow_tpu.operators.profile",
         "--leader-elect", "--leader-elect-name", "smoke-lease",
         "--metrics-port", "0"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        lease = None
        for _ in range(150):
            lease = api.get_or_none("coordination.k8s.io/v1", "Lease",
                                    "smoke-lease", "kubeflow")
            if lease:
                break
            time.sleep(0.2)
        assert lease, "manager never acquired the lease"
        lease["spec"]["holderIdentity"] = "other"
        lease["spec"]["renewTime"] = "2099-01-01T00:00:00.000000Z"
        api.update(lease)
        assert proc.wait(timeout=60) == 1
    finally:
        if proc.poll() is None:
            proc.kill()
        httpd.shutdown()


def test_run_loop_failure_exit_stops_pumps(api):
    """PR-11 regression (tpu-lint thread-lifecycle triage): a reconcile
    loop that died by exception closed its workqueue but never set the
    stop flag — the pump threads' only termination signal — so they
    kept reopening watches and delivering events forever. ANY exit of
    run() now sets the flag and the pumps wind down."""
    ctrl = NotebookController(api)

    def boom(*a, **kw):
        raise RuntimeError("loop death")

    ctrl._queue.get = boom
    with pytest.raises(RuntimeError, match="loop death"):
        ctrl.run()
    assert ctrl._stop.is_set()
    for pump in ctrl._pumps:
        pump.join(timeout=10)
        assert not pump.is_alive(), "pump thread survived loop death"
