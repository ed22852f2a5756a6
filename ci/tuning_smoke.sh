#!/bin/sh
# CI tuning-smoke (ci/pipeline.yaml `tuning-smoke` stage): the self-tuning
# engine must close its loop end-to-end on CPU. It runs one full
# Experiment per policy through the REAL ExperimentController on the fake
# apiserver (kubeflow_tpu/tuning/sweep.py) and exits nonzero when any gate
# trips: non-Succeeded experiment, non-monotone best-so-far trace, no
# improvement over the checked-in defaults (trial 0 is always the
# baseline), missing promotion record, or the bayesian proposer needing
# more than half of random's trials to reach random's final best.
set -e

check_json() {
    printf '%s\n' "$1" | python -c '
import json, sys
text = sys.stdin.read()
start = text.find("{")
if start < 0:
    sys.exit("tuning sweep emitted no JSON")
rec = json.loads(text[start:])  # non-JSON output fails here
if rec.get("regression"):
    reasons = rec.get("reasons")
    sys.exit(f"tuning sweep regression marker set: {reasons}")
for policy, r in rec["policies"].items():
    state = r.get("state")
    if state != "Succeeded":
        sys.exit(f"{policy} experiment ended {state}")
    trace = r.get("bestSoFarTrace") or []
    if not trace or any(b < a for a, b in zip(trace, trace[1:])):
        sys.exit(f"{policy} best-so-far trace missing or not monotone: {trace}")
    if not r.get("improvementPercent") or r["improvementPercent"] <= 0:
        sys.exit(f"{policy} found nothing better than the defaults")
    if not (r.get("promotion") or {}).get("version"):
        sys.exit(f"{policy} promotion not recorded")
'
}

# Search economy on the deterministic synthetic landscape (closed form,
# no clock: a CPU run yields counts, never a time or a ratio of times, so
# the live decode-tps trial is pinned by tests/test_experiment.py on its
# counts and is searched for better knobs only on the chip):
# random (the economy baseline) then GP-EI bayesian; the sweep gates
# bayesian reaching random's final best in <= half the trials, every
# policy beating the defaults, monotone traces, and a recorded
# promotion (versions write onto the fake target InferenceService).
out="$(JAX_PLATFORMS=cpu python -m kubeflow_tpu.tuning.sweep \
    --scenario synthetic-knobs --policies random,bayesianoptimization \
    --trials 12 --seed 7 --promote)"
check_json "$out"
echo "tuning smoke: synthetic-knobs economy gate ok"
echo "tuning smoke ok"
