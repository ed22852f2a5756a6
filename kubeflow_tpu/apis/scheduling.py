"""Cluster-scheduler API types: SchedulingPolicy CRD + placement contract.

The scheduler (:mod:`kubeflow_tpu.scheduler`) owns placement for every
training-job kind. Its API surface is deliberately small:

- a ``SchedulingPolicy`` CR carrying the cluster-wide knobs (scheduling
  period, starvation aging, preemption policy, queue weights, throughput
  profiles) — the scheduler reconciles this object, and every job/pod/node
  event requeues it, so one reconcile == one scheduling round;
- job ``spec.priority`` / ``spec.queue`` / ``spec.profile`` /
  ``spec.preemptible`` fields (schema added in :mod:`~kubeflow_tpu.apis.jobs`)
  that opt a job into scheduler-managed placement;
- annotations that carry decisions between the scheduler and the job
  controller: the gang's reservation lands as ONE ``placement`` annotation
  on the job (all-or-nothing by construction — there is no per-replica
  placement write to half-apply), and preemption marks victims with
  ``preempted-by`` on the job and its pods.

Placement annotation value (JSON)::

    {"pool": "v5e", "topology": "2x4", "slice": "v5e-0",
     "nodes": ["node-a", "node-b"], "decidedAt": "..."}

``nodes`` has exactly one entry per gang pod; the job controller maps pod
*i* of the gang onto ``nodes[i]`` (`spec.nodeName`), replacing the bare GKE
nodeSelector path for managed jobs.
"""

from __future__ import annotations

import json
from typing import Mapping

from kubeflow_tpu.k8s import objects as k8s
from kubeflow_tpu.version import API_GROUP, DEFAULT_NAMESPACE

SCHEDULING_API_VERSION = f"{API_GROUP}/v1"
SCHEDULING_POLICY_KIND = "SchedulingPolicy"
SCHEDULING_POLICY_PLURAL = "schedulingpolicies"

# Node labels the capacity model reads. Accelerator/topology are the GKE
# TPU labels the job controller already targets; the slice label groups
# hosts into one contiguous slice (a gang must land wholly inside one).
NODE_ACCEL_LABEL = "cloud.google.com/gke-tpu-accelerator"
NODE_TOPO_LABEL = "cloud.google.com/gke-tpu-topology"
NODE_SLICE_LABEL = f"{API_GROUP}/slice"

# Decision-carrying annotations (job + pod metadata).
ANN_PLACEMENT = f"{API_GROUP}/placement"
ANN_PREEMPTED_BY = f"{API_GROUP}/preempted-by"
ANN_POOL = f"{API_GROUP}/pool"
ANN_SLICE = f"{API_GROUP}/slice"

# Scheduler-owned job condition types (the job controller's lifecycle
# conditions — Created/Running/… — stay owned by the job controller).
COND_QUEUED = "Queued"
COND_UNSCHEDULABLE = "Unschedulable"

# status.scheduling.state values.
STATE_QUEUED = "Queued"
STATE_ADMITTED = "Admitted"
STATE_PREEMPTED = "Preempted"
STATE_UNSCHEDULABLE = "Unschedulable"

DEFAULT_SCHEDULING_PERIOD_SECONDS = 5.0
DEFAULT_AGING_SECONDS = 300.0
DEFAULT_REQUEUE_BACKOFF_SECONDS = 10.0
DEFAULT_QUEUE = "default"
DEFAULT_QUEUE_WEIGHT = 1.0


def is_managed(job: Mapping) -> bool:
    """A job is scheduler-managed iff it asks for queueing: an explicit
    priority or queue opts in. Unmanaged jobs keep the legacy first-come
    path (bare GKE nodeSelectors), so existing workloads are untouched."""
    spec = job.get("spec", {})
    return spec.get("priority") is not None or bool(spec.get("queue"))


def job_priority(job: Mapping) -> int:
    p = job.get("spec", {}).get("priority")
    return int(p) if p is not None else 0


def job_queue(job: Mapping) -> str:
    return job.get("spec", {}).get("queue") or DEFAULT_QUEUE


def is_preemptible(job: Mapping) -> bool:
    return bool(job.get("spec", {}).get("preemptible", True))


def elastic_spec(job: Mapping) -> dict | None:
    """The job's elastic range — ``{"min": minReplicas, "max":
    maxReplicas}`` in hosts — or None for a fixed-size gang. Declaring
    the range is the job's consent to live resizing: the scheduler may
    grant anywhere inside it and move the grant while the job runs (the
    train loop reshards at the next step boundary). Malformed blocks
    read as non-elastic so the scheduler never resizes on garbage."""
    raw = job.get("spec", {}).get("elastic")
    if not isinstance(raw, Mapping):
        return None
    try:
        lo = int(raw.get("minReplicas", 1))
        hi = int(raw.get("maxReplicas", lo))
    except (TypeError, ValueError):
        return None
    if lo < 1 or hi < lo:
        return None
    return {"min": lo, "max": hi}


def placement(job: Mapping) -> dict | None:
    """Parse the job's placement annotation; None when unplaced (or the
    annotation is malformed — treated as unplaced so the scheduler
    re-decides rather than the job controller acting on garbage)."""
    raw = job.get("metadata", {}).get("annotations", {}).get(ANN_PLACEMENT)
    if not raw:
        return None
    try:
        decided = json.loads(raw)
    except (TypeError, ValueError):
        return None
    if not isinstance(decided, dict) or not decided.get("nodes"):
        return None
    return decided


def encode_placement(pool: str, topology: str, slice_id: str,
                     nodes: list[str], decided_at: str,
                     elastic: Mapping | None = None) -> str:
    """``elastic`` (written for elastic jobs only) carries
    ``{"granted": n, "min": m, "max": M}`` so the training loop can map
    its host grant onto a device count without a second API read: target
    devices = visible devices × granted / max (the pod is provisioned
    for the max grant; parallel/reshard.scaled_mesh_config does the
    axis math)."""
    decided = {
        "pool": pool, "topology": topology, "slice": slice_id,
        "nodes": list(nodes), "decidedAt": decided_at,
    }
    if elastic is not None:
        decided["elastic"] = dict(elastic)
    return json.dumps(decided, sort_keys=True)


def placement_grant(job: Mapping) -> tuple[int, int] | None:
    """(granted, max) hosts from an elastic placement; None when the job
    is unplaced or not elastic. The ratio is the elastic train loop's
    resize signal (train/elastic.py)."""
    decided = placement(job)
    if decided is None:
        return None
    elastic = decided.get("elastic")
    if not isinstance(elastic, Mapping):
        return None
    try:
        granted = int(elastic.get("granted", len(decided["nodes"])))
        cap = int(elastic["max"])
    except (KeyError, TypeError, ValueError):
        return None
    if granted < 1 or cap < granted:
        return None
    return granted, cap


# ---------------------------------------------------------------------------
# SchedulingPolicy CRD
# ---------------------------------------------------------------------------


def scheduling_policy_schema() -> dict:
    return {
        "type": "object",
        "properties": {
            "spec": {
                "type": "object",
                "properties": {
                    "schedulingPeriodSeconds": {
                        "type": "number", "minimum": 0.01},
                    "agingSeconds": {
                        # Seconds of queue wait worth one priority point
                        # (starvation aging); 0 disables aging.
                        "type": "number", "minimum": 0},
                    "preemption": {
                        "type": "object",
                        "properties": {
                            "enabled": {"type": "boolean"},
                            "minPriorityGap": {
                                # A preemptor must outrank its victim by
                                # strictly more than this many points.
                                "type": "integer", "minimum": 0},
                            "requeueBackoffSeconds": {
                                "type": "number", "minimum": 0},
                            "gracePeriodSeconds": {
                                "type": "number", "minimum": 0},
                        },
                    },
                    "elastic": {
                        # Live-resize policy for jobs declaring
                        # spec.elastic: shrink a running elastic victim
                        # (placement rewrite → step-boundary reshard)
                        # before falling back to preemption-by-kill, and
                        # opportunistically grow elastic jobs into idle
                        # capacity left after the queue pass.
                        "type": "object",
                        "properties": {
                            "shrinkBeforePreempt": {"type": "boolean"},
                            "growEnabled": {"type": "boolean"},
                            "growDelaySeconds": {
                                # Quiet period after a shrink before the
                                # same job may grow back (anti-thrash).
                                "type": "number", "minimum": 0},
                        },
                    },
                    "queues": {
                        "type": "array",
                        "items": {
                            "type": "object",
                            "required": ["name"],
                            "properties": {
                                "name": {"type": "string"},
                                "weight": {"type": "number",
                                           "exclusiveMinimum": 0},
                            },
                        },
                    },
                    "profiles": {
                        # profile -> accelerator -> measured throughput
                        # (tokens/s/chip, in the shape a trial prints): the
                        # Gavel-style heterogeneity signal.
                        "type": "object",
                        "x-kubernetes-preserve-unknown-fields": True,
                    },
                },
            },
            "status": {"type": "object",
                       "x-kubernetes-preserve-unknown-fields": True},
        },
    }


def scheduling_policy_crd() -> dict:
    return k8s.crd(
        group=API_GROUP,
        kind=SCHEDULING_POLICY_KIND,
        plural=SCHEDULING_POLICY_PLURAL,
        short_names=["schedpol"],
        categories=["all", "kubeflow-tpu"],
        versions=[
            k8s.crd_version(
                "v1",
                schema=scheduling_policy_schema(),
                served=True,
                storage=True,
                printer_columns=[
                    k8s.printer_column("Queued", ".status.queueDepth"),
                    k8s.printer_column("Age", ".metadata.creationTimestamp",
                                       "date"),
                ],
            ),
        ],
    )


def scheduling_policy(name: str = "default",
                      namespace: str = DEFAULT_NAMESPACE,
                      **spec) -> dict:
    return {
        "apiVersion": SCHEDULING_API_VERSION,
        "kind": SCHEDULING_POLICY_KIND,
        "metadata": k8s.metadata(name, namespace),
        "spec": spec,
    }


def policy_knobs(policy: Mapping) -> dict:
    """Resolve a policy spec into a flat knob dict with defaults."""
    spec = policy.get("spec", {}) if policy else {}
    preemption = spec.get("preemption", {}) or {}
    elastic = spec.get("elastic", {}) or {}
    weights = {DEFAULT_QUEUE: DEFAULT_QUEUE_WEIGHT}
    for q in spec.get("queues", []) or []:
        if isinstance(q, Mapping) and q.get("name"):
            weights[q["name"]] = float(q.get("weight",
                                             DEFAULT_QUEUE_WEIGHT))
    return {
        "period": float(spec.get("schedulingPeriodSeconds",
                                 DEFAULT_SCHEDULING_PERIOD_SECONDS)),
        "aging_seconds": float(spec.get("agingSeconds",
                                        DEFAULT_AGING_SECONDS)),
        "preemption_enabled": bool(preemption.get("enabled", True)),
        "min_priority_gap": int(preemption.get("minPriorityGap", 0)),
        "requeue_backoff": float(preemption.get(
            "requeueBackoffSeconds", DEFAULT_REQUEUE_BACKOFF_SECONDS)),
        "grace_seconds": float(preemption.get("gracePeriodSeconds", 30.0)),
        "shrink_enabled": bool(elastic.get("shrinkBeforePreempt", True)),
        "grow_enabled": bool(elastic.get("growEnabled", True)),
        "grow_delay": float(elastic.get("growDelaySeconds", 0.0)),
        "queue_weights": weights,
        "profiles": dict(spec.get("profiles", {}) or {}),
    }
