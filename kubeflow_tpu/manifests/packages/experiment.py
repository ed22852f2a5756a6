"""Experiment package: the self-tuning engine's CRD + RBAC + example CR.

Katib's Experiment layered over kubebench measured runs, fused into one
CRD (see apis/experiment.py). The controller itself rides in the
training-operator manager (operators/__main__.py) — this package ships
what a cluster needs to admit Experiments: the CRD, a ClusterRole that
can run trials (JaxJobs) and promote winners (InferenceService spec
writes), and a worked example CR tuning the decode-tps scenario.
"""

from __future__ import annotations

from kubeflow_tpu.apis.experiment import experiment, experiment_crd
from kubeflow_tpu.k8s import objects as k8s
from kubeflow_tpu.manifests.core import ParamSpec, prototype
from kubeflow_tpu.version import API_GROUP, DEFAULT_NAMESPACE


@prototype(
    "experiment",
    "Experiment CRD + RBAC + example CR: knob search over a serving "
    "scenario, winner promoted through the rollout controller",
    params=[
        ParamSpec("name", "decode-knobs"),
        ParamSpec("namespace", DEFAULT_NAMESPACE),
        ParamSpec("scenario", "decode-tps"),
        ParamSpec("algorithm", "tpe",
                  "random|grid|hyperband|bayesianoptimization|tpe"),
        ParamSpec("max_trials", 12),
        ParamSpec("seed", 0),
        ParamSpec("target", "", "InferenceService the winner promotes to"),
    ],
)
def experiment_package(name: str, namespace: str, scenario: str,
                       algorithm: str, max_trials: int, seed: int,
                       target: str) -> list[dict]:
    rbac_name = "experiment-controller"
    labels = {"app": rbac_name}
    promotion = {"target": target, "minImprovementPercent": 1.0} \
        if target else None
    return [
        experiment_crd(),
        k8s.service_account(rbac_name, namespace, labels),
        k8s.cluster_role(
            rbac_name,
            [
                k8s.policy_rule(
                    [API_GROUP],
                    ["experiments", "experiments/status"], ["*"]),
                # Trials are preemptible JaxJobs.
                k8s.policy_rule(
                    [API_GROUP], ["jaxjobs", "jaxjobs/status"], ["*"]),
                # Promotion writes the candidate version onto the target
                # InferenceService; the rollout controller walks it.
                k8s.policy_rule(
                    [API_GROUP],
                    ["inferenceservices", "inferenceservices/status"],
                    ["get", "list", "watch", "update", "patch"]),
                k8s.policy_rule([""], ["events"], ["create", "patch"]),
            ],
            labels,
        ),
        k8s.cluster_role_binding(rbac_name, rbac_name, rbac_name,
                                 namespace),
        experiment(
            name, namespace, scenario,
            algorithm=algorithm,
            max_trials=int(max_trials),
            seed=int(seed),
            promotion=promotion,
        ),
    ]
