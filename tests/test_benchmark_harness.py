"""Tier-1 counts the benchmark's own CPU tests: ``benchmarks/tests`` lies
outside ``tests/``, so its cases are imported here (the benchmark's
contract, generators, arithmetic and trace reductions on hand-made events,
the drivers on the rehearsal configurations with their planted faults, and
the span readers)."""

from benchmarks.tests.test_harness import *  # noqa: F401,F403
from benchmarks.tests.test_spans import *  # noqa: F401,F403
from benchmarks.tests.test_sessions import *  # noqa: F401,F403
from benchmarks.tests.test_looped import *  # noqa: F401,F403
from benchmarks.tests.test_rounds import *  # noqa: F401,F403
from benchmarks.tests.test_latent_moe import *  # noqa: F401,F403

# The latent-attention cell's per-layer metrics, appended after the round's
# three in BENCHMARK.json, in the order they are listed.
LATENT_MOE_METRICS = ("step_mfu_pct.kanana", "kanana_decode_roofline",
                      "moe_experts_roofline", "latent_attn_roofline",
                      "moe_route_share_pct", "experts_touched_pct")


# This REPLACES the check of the same name that the import of test_rounds
# brings: that one asks for the round's three metrics to be the last entries
# of ``per_layer``, which no longer holds once later metrics are appended,
# and its file is the harness's own, relaxed only with the harness
# (ROADMAP.md, D16). Run directly, ``benchmarks/tests`` still fails it.
def test_the_round_metrics_are_listed_last_with_their_cells():
    """The round's three metrics are listed together, in order, on
    ``chat-steady`` alone, and followed by exactly the latent-attention
    cell's six, each on that cell alone."""
    import os

    from benchmarks.harness import registry
    from benchmarks.tests.test_rounds import CHAT_STEADY, REPO, ROUND_METRICS

    per_layer = registry.benchmark_json()["per_layer"]
    tail = len(ROUND_METRICS) + len(LATENT_MOE_METRICS)
    listed = per_layer[-tail:-len(LATENT_MOE_METRICS)]
    assert [m["name"] for m in listed] == list(ROUND_METRICS)
    for entry in listed:
        assert entry["layer"] == "scheduler"
        assert entry["source"] == "program_span"
        assert entry["better"] == "lower"
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "metrics", entry["name"] + ".py"))
    assert all(m["workloads"] == [CHAT_STEADY] for m in listed)
    assert [m["moves"] for m in listed] == [
        "itl_p95_ms", "itl_p95_ms", "serve_tokens_per_s"]
    after = per_layer[-len(LATENT_MOE_METRICS):]
    assert [m["name"] for m in after] == list(LATENT_MOE_METRICS)
    assert all(m["workloads"] == ["kanana-2-30b-a3b-serve.moe-decode"]
               for m in after)
