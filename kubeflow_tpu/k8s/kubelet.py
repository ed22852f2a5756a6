"""Local pod executor for the fake cluster — the E2E "fake kubelet".

The reference's CI gets real-workload coverage by provisioning actual
clusters per run (testing/install_minikube.sh, testing/deploy_kubeflow.py:49
on a GCE VM); nothing in its tree can run a workload without one. This module
closes that gap for the fake apiserver: it schedules Pending pods by
launching their container command as a local subprocess — with the
operator-injected rendezvous env rewritten to loopback — and mirrors the
process result into pod status, so controller E2E tests (JaxJob gang →
`jax.distributed.initialize` → psum → Succeeded) run multi-process on one
machine with no cluster and no TPUs (SURVEY.md §4: the multi-node-without-
hardware capability the reference lacks).

Scope: one container per pod, command+args+env only (no volumes, probes, or
images — the command runs against the repo's own interpreter). That is
exactly the surface the training operators exercise.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

from kubeflow_tpu.k8s.client import ApiError, K8sClient

POD_API = "v1"

# Env vars whose values embed pod DNS hostnames (``pod.job.ns[:port]``) that
# only resolve inside a cluster; the kubelet rewrites the host part to
# loopback so every process rendezvouses on the local machine.
_ADDRESS_ENV = (
    "JAX_COORDINATOR_ADDRESS",
    "MEGASCALE_COORDINATOR_ADDRESS",
    "MASTER_ADDR",
    "DMLC_PS_ROOT_URI",
    "CHAINERMN_MASTER_ADDR",
)

# Env vars holding a bare rendezvous port. On real pod IPs every gang can
# bind the same well-known port; mapped onto ONE loopback host they
# collide across concurrently-running (or TIME_WAIT-lingering) gangs, so
# the kubelet remaps each gang's ports to free ones — consistently for
# every pod of the gang, and consistently with the ports embedded in the
# _ADDRESS_ENV values.
_PORT_ENV = (
    "JAX_COORDINATOR_PORT",
    "MASTER_PORT",
    "DMLC_PS_ROOT_PORT",
    "CHAINERMN_MASTER_PORT",
)


def _loopback(value: str) -> str:
    """``host[:port]`` → ``127.0.0.1[:port]`` (host part dropped)."""
    host, sep, port = value.partition(":")
    return f"127.0.0.1{sep}{port}" if sep else "127.0.0.1"


# Tail of a pod's output kept in status.log (the kubectl-logs analogue).
# Matches the 64KB spool window so a few-hundred-step per-step training
# log survives whole — the preemption-resume AND elastic-shrink E2Es
# read every per-step loss (and the reshard event line) out of it.
_LOG_TAIL = 65536


@dataclass
class _Running:
    proc: subprocess.Popen
    pod_name: str
    namespace: str
    # stdout spools to an unlinked temp file, not a PIPE: a pod writing more
    # than the ~64KB pipe buffer would otherwise block on write until the
    # kubelet timeout kills it (verbose-but-healthy workloads would fail).
    out_file: object = None
    # (namespace, owning job) — keys the gang's remapped rendezvous ports.
    gang: tuple | None = None
    started: float = field(default_factory=time.monotonic)


class FakeKubelet:
    """Runs Pending pods from a :class:`FakeApiServer` as local subprocesses.

    ``extra_env`` is overlaid on every container (tests use it to force the
    virtual CPU platform); ``cpu_devices_per_pod`` provisions that many JAX
    CPU devices per process so an N-pod gang forms an N×M-device slice.
    """

    def __init__(
        self,
        client: K8sClient,
        *,
        extra_env: dict[str, str] | None = None,
        cpu_devices_per_pod: int | None = None,
        timeout: float = 120.0,
    ) -> None:
        self.client = client
        self.extra_env = dict(extra_env or {})
        self.cpu_devices_per_pod = cpu_devices_per_pod
        self.timeout = timeout
        self._running: dict[tuple[str, str], _Running] = {}
        # (namespace, owning-job, original-port) -> remapped free port.
        self._gang_ports: dict[tuple[str, str, str], int] = {}
        self._stop = threading.Event()

    @staticmethod
    def _gang_key(pod: dict) -> tuple[str, str]:
        refs = pod["metadata"].get("ownerReferences") or []
        owner = refs[0]["name"] if refs else pod["metadata"]["name"]
        return (pod["metadata"].get("namespace", ""), owner)

    def _gang_port(self, pod: dict, orig: str) -> int:
        """A free local port for this gang's ``orig`` rendezvous port,
        stable across every pod sharing the owning job (one generation;
        entries are pruned when the gang's last pod is reaped, so a
        restarted gang gets fresh ports instead of inheriting a slot
        something else may hold by now)."""
        key = (*self._gang_key(pod), orig)
        port = self._gang_ports.get(key)
        if port is None:
            import socket

            issued = set(self._gang_ports.values())
            while True:
                with socket.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    port = s.getsockname()[1]
                if port not in issued:
                    break  # never hand two gangs the same port
            self._gang_ports[key] = port
        return port

    def _prune_gang_ports(self, gang: tuple[str, str] | None) -> None:
        """Drop a gang's port mappings once none of its pods run."""
        if gang is None:
            return
        if any(r.gang == gang for r in self._running.values()):
            return
        self._gang_ports = {k: v for k, v in self._gang_ports.items()
                            if k[:2] != gang}

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------

    def _child_env(self, pod: dict) -> dict[str, str]:
        env = dict(os.environ)
        # Local-process pods never take the accelerator.
        env["JAX_PLATFORMS"] = "cpu"
        if self.cpu_devices_per_pod:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count="
                f"{self.cpu_devices_per_pod}"
            ).strip()
        container = pod["spec"]["containers"][0]
        for item in container.get("env", []):
            name, value = item["name"], str(item.get("value", ""))
            if name in _ADDRESS_ENV:
                value = _loopback(value)
                host, sep, port = value.partition(":")
                if sep and port.isdigit():
                    value = f"{host}:{self._gang_port(pod, port)}"
            elif name in _PORT_ENV and value.isdigit():
                value = str(self._gang_port(pod, value))
            env[name] = value
        env.update(self.extra_env)
        return env

    def _spawn(self, pod: dict) -> None:
        container = pod["spec"]["containers"][0]
        argv = list(container.get("command", []))
        argv += [str(a) for a in container.get("args", [])]
        if argv and argv[0] in ("python", "python3"):
            argv[0] = sys.executable
        if not argv:
            self._set_phase(pod, "Failed", exit_code=127,
                            log="container has no command or args")
            return
        out_file = tempfile.TemporaryFile()  # binary: tail-seek is exact
        try:
            proc = subprocess.Popen(
                argv,
                env=self._child_env(pod),
                stdout=out_file,
                stderr=subprocess.STDOUT,
            )
        except (OSError, ValueError) as e:  # nonexistent binary, bad argv …
            out_file.close()
            self._set_phase(pod, "Failed", exit_code=127, log=str(e))
            return
        key = (pod["metadata"]["namespace"], pod["metadata"]["name"])
        self._running[key] = _Running(proc, key[1], key[0],
                                      out_file=out_file,
                                      gang=self._gang_key(pod))
        self._set_phase(pod, "Running")

    def _set_phase(self, pod: dict, phase: str,
                   exit_code: int | None = None, log: str = "",
                   reason: str | None = None,
                   disruption_target: bool = False) -> None:
        name = pod["metadata"]["name"]
        ns = pod["metadata"]["namespace"]
        try:
            current = self.client.get(POD_API, "Pod", name, ns)
        except ApiError:
            return  # pod deleted under us (gang restart / job teardown)
        status = current.setdefault("status", {})
        status["phase"] = phase
        if reason is not None:
            status["reason"] = reason
        if disruption_target:
            # The condition the eviction API sets on a real cluster —
            # one of the signals JobController._is_preempted keys on.
            conds = [c for c in status.get("conditions", [])
                     if c.get("type") != "DisruptionTarget"]
            conds.append({"type": "DisruptionTarget", "status": "True",
                          "reason": reason or "EvictionByEvictionAPI"})
            status["conditions"] = conds
        if exit_code is not None:
            container = current["spec"]["containers"][0]
            status["containerStatuses"] = [{
                "name": container.get("name", "main"),
                "state": {"terminated": {"exitCode": exit_code}},
            }]
        if log:
            status["log"] = log[-_LOG_TAIL:]
        self.client.update_status(current)

    @staticmethod
    def _read_tail(run: "_Running") -> str:
        """Drain the pod's spooled output (last 64KB) and close the file."""
        if run.out_file is None:
            return ""
        out = FakeKubelet._peek_tail(run)
        run.out_file.close()
        return out

    @staticmethod
    def _peek_tail(run: "_Running") -> str:
        """The pod's spooled output so far (last 64KB) WITHOUT closing —
        live-log streaming for still-running pods (the `kubectl logs`
        view tests use to observe a training loop mid-run)."""
        size = run.out_file.seek(0, 2)
        run.out_file.seek(max(0, size - 65536))
        return run.out_file.read().decode("utf-8", "replace")

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def step(self) -> int:
        """One scheduling pass: start Pending pods, reap finished ones.
        Returns the number of still-running pods."""
        for pod in self.client.list(POD_API, "Pod"):
            key = (pod["metadata"]["namespace"], pod["metadata"]["name"])
            phase = pod.get("status", {}).get("phase", "Pending")
            if phase == "Pending" and key not in self._running:
                self._spawn(pod)
        for key, run in list(self._running.items()):
            rc = run.proc.poll()
            if rc is None:
                if time.monotonic() - run.started > self.timeout:
                    run.proc.kill()
                    run.proc.wait()  # reap; also flushes remaining output
                    rc = -9
                else:
                    # Live log streaming: publish the output tail while
                    # the pod runs, so observers (tests, the dashboard)
                    # can follow a long-running workload without waiting
                    # for exit.
                    out = self._peek_tail(run)
                    if out:
                        pod = self.client.get_or_none(
                            POD_API, "Pod", key[1], key[0])
                        if (pod is not None
                                and (pod.get("status", {}).get("log")
                                     or "") != out[-_LOG_TAIL:]):
                            self._set_phase(pod, "Running", log=out)
                    continue
            # Only the tail survives into status.log — don't materialize
            # a long-running pod's full output.
            out = self._read_tail(run)
            pod = {"metadata": {"namespace": key[0], "name": key[1]}}
            try:
                pod = self.client.get(POD_API, "Pod", key[1], key[0])
            except ApiError:
                pod = None
            if pod is not None:
                self._set_phase(
                    pod, "Succeeded" if rc == 0 else "Failed",
                    exit_code=rc, log=out,
                )
            gang = run.gang
            del self._running[key]
            self._prune_gang_ports(gang)
        return len(self._running)

    # A real kubelet's default grace when neither the eviction request nor
    # the pod spec names one.
    DEFAULT_GRACE_SECONDS = 30.0

    def evict(self, name: str, namespace: str = "kubeflow",
              reason: str = "Preempted",
              grace_seconds: float | None = None) -> bool:
        """Eviction delivered the way a real kubelet does: SIGTERM first,
        then a grace window for the workload to finish its in-flight step
        and save (the train loop's graceful-shutdown path), then SIGKILL.
        ``grace_seconds=None`` honors the pod's own
        ``spec.terminationGracePeriodSeconds`` (default 30) — so the
        gang-coordinated checkpoint path is exercised by eviction exactly
        as the pod requested it, not by a hand-picked test constant.

        The pod is marked Failed with ``reason`` plus a DisruptionTarget
        condition — the signals the JobController's gang logic keys
        preemption handling on (restart without burning backoffLimit) —
        regardless of how the process exited, matching what a reclaimed
        node reports.

        Returns False without killing anything if the pod is not actively
        running (already finished or never started): fabricating a
        preemption on a completed pod would make the controller restart a
        job that succeeded. A finished-but-unreaped process is left for
        ``step()`` to reap with its real exit status."""
        import subprocess

        key = (namespace, name)
        run = self._running.get(key)
        if run is None or run.proc.poll() is not None:
            return False
        if grace_seconds is None:
            try:
                pod_spec = self.client.get(POD_API, "Pod", name,
                                           namespace).get("spec", {})
            except ApiError:
                pod_spec = {}
            grace_seconds = float(pod_spec.get(
                "terminationGracePeriodSeconds",
                self.DEFAULT_GRACE_SECONDS))
        del self._running[key]
        self._prune_gang_ports(run.gang)
        run.proc.terminate()  # SIGTERM: the grace window starts
        try:
            rc = run.proc.wait(timeout=max(0.0, grace_seconds))
        except subprocess.TimeoutExpired:
            run.proc.kill()
            run.proc.wait()
            rc = 137
        log = self._read_tail(run)  # always drain+close the spool
        try:
            pod = self.client.get(POD_API, "Pod", name, namespace)
        except ApiError:
            return True  # evicted; pod object deleted concurrently
        self._set_phase(pod, "Failed", exit_code=rc, log=log,
                        reason=reason, disruption_target=True)
        return True

    def evict_node(self, node_name: str, *,
                   grace_seconds: float | None = None,
                   reason: str = "NodeShutdown") -> list[str]:
        """Node-kill churn helper: evict every running pod bound to
        ``node_name`` (spec.nodeName), the way a reclaimed host takes its
        whole gang share down at once. Returns the evicted pod names."""
        evicted = []
        for key, run in list(self._running.items()):
            try:
                pod = self.client.get(POD_API, "Pod", run.pod_name,
                                      run.namespace)
            except ApiError:
                continue
            if pod.get("spec", {}).get("nodeName") != node_name:
                continue
            if self.evict(run.pod_name, run.namespace, reason=reason,
                          grace_seconds=grace_seconds):
                evicted.append(run.pod_name)
        return evicted

    def run_until_idle(self, *, reconcile=None, deadline: float = 180.0,
                       poll: float = 0.2) -> None:
        """Drive scheduling (and an optional controller ``reconcile_all``
        callback) until no pod is Pending or Running, or the deadline hits."""
        end = time.monotonic() + deadline
        while time.monotonic() < end:
            running = self.step()
            if reconcile is not None:
                reconcile()
            pending = [
                p for p in self.client.list(POD_API, "Pod")
                if p.get("status", {}).get("phase", "Pending")
                in ("Pending", "Running")
            ]
            if not pending and not running:
                return
            time.sleep(poll)
        raise TimeoutError(
            f"pods still active after {deadline}s: "
            f"{[(r.namespace, r.pod_name) for r in self._running.values()]}"
        )

    def shutdown(self) -> None:
        for run in self._running.values():
            if run.proc.poll() is None:
                run.proc.kill()
                run.proc.wait()  # reap — no zombies across a test session
            if run.out_file is not None:
                run.out_file.close()
        self._running.clear()
