"""Named serving scenarios: the registry the Experiment operator's trials run.

A :class:`Scenario` couples a **trial** (``fn(assignments, *, seed, model,
quick) -> dict``: knob overrides in, objectives out) with its **knob
search space** (katib-style parameter dicts over the engine knobs it
honors) and the **checked-in defaults** those knobs hold today. The
defaults ARE the baseline an experiment must beat. A trial drives the
SAME serving stack the production replica runs and reads its objectives
from the histogram exposition through the autoscaler's ``scrape_signals``
reduction, so a tuned config wins on the numbers the SLO gates judge.

Trial reproducibility: every stochastic choice a trial makes (traffic
mix, prompt lengths, decode lengths) is drawn from ONE
``np.random.default_rng(seed)``. Re-running a trial with its recorded
seed observes the same trace, so a preempted trial re-runs instead of
poisoning the objective with a half-measured sample.

In-process trials call :func:`run_trial`; a job-mode trial's container
runs this module (``python -m kubeflow_tpu.serving.scenarios --scenario
<s> --seed <n> [--quick] --assignments <json>``) and the operator reads
the trial dict from the last line of its output.
"""

from __future__ import annotations

import argparse
import json
import math
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Mapping

import numpy as np

from kubeflow_tpu.utils.jaxenv import (
    device_summary,
    place_compile_cache,
    require_tpu,
)


def percentile(sorted_vals, p):
    """Nearest-rank percentile over an ascending list: the value at rank
    ``ceil(p/100 * n)`` (1-based). The previous ``int(n*p/100)`` index
    read one element high on exact-rank hits — p50 of an even-length
    list returned the upper middle element."""
    rank = math.ceil(len(sorted_vals) * p / 100)
    return sorted_vals[max(rank, 1) - 1]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Scenario:
    """One named workload: ``trial`` takes knob assignments and returns
    the objective vector."""

    name: str
    description: str
    trial: Callable
    # Knob search space (katib-style parameter dicts) and the checked-in
    # defaults those knobs hold today — the experiment's baseline.
    parameters: list = field(default_factory=list)
    defaults: dict = field(default_factory=dict)
    # Default objective for experiments over this scenario.
    objective: str = "tokens_per_sec"
    optimization: str = "maximize"


_REGISTRY: dict[str, Scenario] = {}


def register(scenario: Scenario) -> Scenario:
    if scenario.name in _REGISTRY:
        raise ValueError(f"scenario {scenario.name!r} already registered")
    _REGISTRY[scenario.name] = scenario
    return scenario


def get_scenario(name: str) -> Scenario:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scenario {name!r}; available {sorted(_REGISTRY)}")


def run_trial(name: str, assignments: Mapping | None = None, *,
              seed: int = 0, model: str = "lm-test-tiny",
              quick: bool = True) -> dict:
    """Run one tuning trial of ``name`` with knob ``assignments`` over
    the scenario's checked-in defaults. Returns the trial dict:
    ``objectives`` (the scrape_signals vector + throughput/KV numbers),
    ``seed``, ``assignments``, and the ThroughputBook-ingestable
    ``config``/``tokens_per_sec_per_chip`` pair."""
    return get_scenario(name).trial(
        dict(assignments or {}), seed=int(seed), model=model,
        quick=bool(quick))


# ---------------------------------------------------------------------------
# Objective plumbing: exposition text -> signal vector
# ---------------------------------------------------------------------------


def decoder_exposition(decoder) -> str:
    """The continuous decoder's metrics as ONE exposition page — the
    same families the model server serves on
    ``/monitoring/prometheus/metrics`` (histograms from the decoder's
    own registry, KV/queue gauges from its counter snapshot), so a
    trial's objective read is byte-compatible with the production
    scrape path."""
    from kubeflow_tpu.observability.metrics import render_prometheus

    m = decoder.metrics()
    return decoder.registry.render() + render_prometheus({
        "serving_requests_total": m.get("requests_admitted", 0),
        "serving_errors_total": 0,
        "serving_tokens_emitted_total": m.get("tokens_emitted", 0),
        "serving_queued": m.get("queued", 0),
        "serving_kv_bytes_in_use": m.get("kv_bytes_in_use", 0),
        "serving_kv_bytes_total": m.get("kv_bytes_total", 0),
    })


def trial_objectives(decoder, tokens_emitted: int, wall_s: float) -> dict:
    """Reduce a finished trial's decoder to the objective vector: the
    autoscaler's scrape_signals p99s (TTFT, inter-token, queue wait),
    KV fill, plus throughput and peak KV bytes."""
    from kubeflow_tpu.operators.inference import scrape_signals

    sig = scrape_signals(decoder_exposition(decoder))
    m = decoder.metrics()
    block_bytes = (m.get("kv_bytes_per_token", 0)
                   * m.get("kv_block_size", 0))
    return {
        "tokens_per_sec": round(tokens_emitted / max(wall_s, 1e-9), 2),
        "ttft_p99_s": round(sig["ttft_p99_s"], 6),
        "inter_token_p99_s": round(sig["inter_token_p99_s"], 6),
        "queue_wait_p99_s": round(sig["queue_wait_p99_s"], 6),
        "kv_utilization": round(sig["kv_utilization"], 4),
        "kv_bytes_peak": int(m.get("kv_blocks_peak", 0) * block_bytes),
        "kv_blocks_in_use_after_drain": int(m.get("kv_blocks_in_use", 0)),
    }


# ---------------------------------------------------------------------------
# decode-tps: the fast-path trial scenario
# ---------------------------------------------------------------------------

# The checked-in defaults (the baseline a tuner must beat): the paged
# pool is sized for 16 worst-case sequences and HELD CONSTANT across
# trials — tuning reapportions a fixed HBM budget (slots admitted
# against it, block granularity, prefill bucketing), it never buys more
# memory. slots=4 is today's conservative admission default.
DECODE_TPS_DEFAULTS = {
    "slots": 4,
    "kv_block_size": 16,
    "prefill_len_buckets": 0,
}

DECODE_TPS_PARAMETERS = [
    {"name": "slots", "parameterType": "int",
     "feasibleSpace": {"min": 2, "max": 16}},
    {"name": "kv_block_size", "parameterType": "int",
     "feasibleSpace": {"min": 4, "max": 24}},
    {"name": "prefill_len_buckets", "parameterType": "int",
     "feasibleSpace": {"min": 0, "max": 4}},
]

_POOL_SEQ_EQUIV = 16  # fixed pool: bytes for 16 worst-case sequences


def _decode_tps_trial(assignments: dict, *, seed: int = 0,
                      model: str = "lm-test-tiny",
                      quick: bool = True) -> dict:
    """Mixed-length decode throughput at a FIXED KV pool budget. Knobs
    reapportion the pool; seeded traffic makes a re-run observe the
    same trace."""
    import jax

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.serving.continuous import ContinuousDecoder

    knobs = {**DECODE_TPS_DEFAULTS, **assignments}
    slots = max(1, int(knobs["slots"]))
    buckets = max(0, int(knobs["prefill_len_buckets"]))

    spec = get_model(model)
    params = spec.init(jax.random.PRNGKey(0), spec.config)
    gen = 8
    prefill_len = 40
    total = prefill_len + gen
    # Legalize the block size: the paged layout needs block | total (the
    # equal-virtual-row-width invariant). Snap DOWN to the nearest
    # divisor, so neighboring proposals land on the same legal plateau
    # rather than erroring out of the search.
    want_block = max(1, int(knobs["kv_block_size"]))
    block = max(b for b in range(1, want_block + 1) if total % b == 0)
    pool_blocks = _POOL_SEQ_EQUIV * (total // block)
    n = 24 if quick else 96
    offered = min(n, 16)

    rng = np.random.default_rng(seed)
    requests = [
        ([int(3 + rng.integers(7))] * int(rng.integers(4, 12)),
         int(rng.integers(2, gen + 1)))
        for _ in range(n)
    ]

    d = ContinuousDecoder(
        params, spec.config, slots=slots, prefill_len=prefill_len,
        max_new_tokens=gen, prefill_len_buckets=buckets,
        kv_layout="paged", kv_block_size=block,
        kv_pool_blocks=pool_blocks, stream_timeout_s=300.0)
    try:
        def one(req):
            toks, want = req
            return len(d.submit(toks, want).result(timeout=300)["tokens"])

        # Untimed warm pass over the SAME trace: compiles for every
        # admission-batch bucket this knob setting will hit land here,
        # so the timed pass measures the steady state each config is
        # compared at (not how many executables it had to build).
        with ThreadPoolExecutor(offered) as pool:
            list(pool.map(one, requests))
        t0 = time.perf_counter()
        with ThreadPoolExecutor(offered) as pool:
            emitted = sum(pool.map(one, requests))
        wall = time.perf_counter() - t0
        objectives = trial_objectives(d, emitted, wall)
    finally:
        d.stop()

    return {
        "scenario": "decode-tps",
        "seed": int(seed),
        "assignments": dict(assignments),
        "objectives": objectives,
        # ThroughputBook ingest contract (scheduler/capacity.py): the
        # profile name is the first whitespace token of ``config``.
        "config": (f"decode-tps slots{slots} block{block} "
                   f"buckets{buckets} pool{pool_blocks} n{n} seed{seed}"),
        "tokens_per_sec_per_chip": objectives["tokens_per_sec"],
    }


# ---------------------------------------------------------------------------
# synthetic-knobs: closed-form trial for CI sweeps and policy tests
# ---------------------------------------------------------------------------

SYNTHETIC_DEFAULTS = {"slots": 4, "kv_block_size": 16}

SYNTHETIC_PARAMETERS = [
    {"name": "slots", "parameterType": "int",
     "feasibleSpace": {"min": 2, "max": 16}},
    {"name": "kv_block_size", "parameterType": "int",
     "feasibleSpace": {"min": 4, "max": 32}},
]


def _synthetic_trial(assignments: dict, *, seed: int = 0,
                     model: str = "", quick: bool = True) -> dict:
    """Closed-form objective surface over the decode-tps knob space —
    a smooth unimodal ridge whose optimum sits away from the checked-in
    defaults, plus a small seed-deterministic noise term. Instant and
    exactly reproducible: the policy-economy gates (bayesian reaching
    random's best in half the trials; monotone best traces) are judged
    here, where no wall-clock jitter can flake them."""
    knobs = {**SYNTHETIC_DEFAULTS, **assignments}
    u_slots = (float(knobs["slots"]) - 2.0) / 14.0
    u_block = (float(knobs["kv_block_size"]) - 4.0) / 28.0
    ridge = math.exp(-((u_slots - 0.75) ** 2
                       + (u_block - 0.40) ** 2) / 0.18)
    noise = float(np.random.default_rng(
        seed * 1_000_003 + int(knobs["slots"]) * 31
        + int(knobs["kv_block_size"])).normal(0.0, 0.003))
    tps = round(100.0 * ridge + noise, 4)
    return {
        "scenario": "synthetic-knobs",
        "seed": int(seed),
        "assignments": dict(assignments),
        "objectives": {
            "tokens_per_sec": tps,
            "ttft_p99_s": round(0.05 / (0.2 + ridge), 6),
            "inter_token_p99_s": round(0.01 / (0.2 + ridge), 6),
            "queue_wait_p99_s": 0.0,
            "kv_utilization": round(min(u_slots + 0.1, 1.0), 4),
            "kv_bytes_peak": int(4096 * (1 + u_block)),
            "kv_blocks_in_use_after_drain": 0,
        },
        "config": (f"synthetic-knobs slots{knobs['slots']} "
                   f"block{knobs['kv_block_size']} seed{seed}"),
        "tokens_per_sec_per_chip": tps,
    }


# ---------------------------------------------------------------------------
# Registrations
# ---------------------------------------------------------------------------

register(Scenario(
    name="decode-tps",
    description="Mixed-length decode throughput at a fixed KV pool "
                "budget; knobs reapportion the pool (slots, block size, "
                "prefill bucketing).",
    trial=_decode_tps_trial,
    parameters=DECODE_TPS_PARAMETERS,
    defaults=dict(DECODE_TPS_DEFAULTS),
    objective="tokens_per_sec",
    optimization="maximize",
))

register(Scenario(
    name="synthetic-knobs",
    description="Closed-form objective over the decode-tps knob space; "
                "instant and seed-deterministic (policy-economy gates "
                "and CI sweeps are judged here).",
    trial=_synthetic_trial,
    parameters=SYNTHETIC_PARAMETERS,
    defaults=dict(SYNTHETIC_DEFAULTS),
    objective="tokens_per_sec",
    optimization="maximize",
))


# ---------------------------------------------------------------------------
# The job-mode trial's entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one trial of a scenario.")
    ap.add_argument("--scenario", required=True)
    ap.add_argument("--seed", type=int, default=0,
                    help="threads through the scenario's traffic "
                         "generation, so a re-run observes the same trace")
    ap.add_argument("--quick", action="store_true",
                    help="the tiny preset on whatever backend JAX finds")
    ap.add_argument("--assignments", default="",
                    help="JSON knob assignments; empty = the checked-in "
                         "defaults")
    args = ap.parse_args(argv)

    # --quick is the tiny preset wherever JAX runs (a smoke of the
    # plumbing); anything else is a measurement and needs the TPU. It
    # never substitutes the CPU.
    place_compile_cache()
    device = device_summary() if args.quick else require_tpu()
    result = run_trial(
        args.scenario,
        json.loads(args.assignments) if args.assignments else {},
        seed=args.seed, model="lm-test-tiny" if args.quick else "llama-1b",
        quick=args.quick)
    result["device"] = device
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
