#!/bin/sh
# CI exposition lint (ci/pipeline.yaml `metrics-lint` stage): boot every
# /metrics surface in-process — model server (decoder driven), gateway
# admin, availability prober, operator HealthServer (with one real
# scheduling round driven so the scheduler_* decision families carry
# samples and their names are asserted present) — scrape each over
# real HTTP, and validate TYPE lines, label escaping and histogram
# bucket ordering with the pure-python promtool-style checker. Exactly
# one renderer (kubeflow_tpu/observability/metrics.py) may know the
# exposition text format; this stage is what keeps a fifth hand-rolled
# renderer from creeping back in.
set -e

JAX_PLATFORMS=cpu python -m kubeflow_tpu.observability.lint --self-check

# The single-renderer invariant, checked at the AST level by the
# tpu-lint exposition checker (kubeflow_tpu/analysis/exposition.py):
# no "# TYPE" string literal outside the allowed renderer modules —
# every exporter must go through the shared renderer, and tests assert
# via its type_line(). The AST scan replaces the old grep: it sees
# through f-strings and concatenation, and it cannot be fooled by the
# phrase appearing in comments or docs. Scope matches the old gate
# (package + tests); the full rule suite over kubeflow_tpu/
# runs in the separate static-analysis stage.
# tests/*.py (not tests/fixtures/ — the analysis bad-fixtures contain
# a deliberate hand-rolled renderer the checker suite asserts on).
JAX_PLATFORMS=cpu python -m kubeflow_tpu.analysis \
    --rules metrics-type-literal \
    kubeflow_tpu tests/*.py
echo "single-renderer invariant ok"
