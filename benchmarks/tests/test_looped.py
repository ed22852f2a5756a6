"""CPU tests of what the looped driver and the loop's counts add to the
benchmark: the new cell's entry, files and traffic, the counts against
hand-worked values at the published widths, every new reader on a
synthetic trace (and silent where a program sets none of its names), and
the driver on the rehearsal configuration, ``correct`` as the program
stands and over the limit with each loop fault planted.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_looped.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import loop, registry, spans, traffic  # noqa: E402

CELL = "ouro-2.6b-serve.reason-decode"
REHEARSAL = "rehearsal-tiny-ouro.reason-decode-tiny"
LOOP_READERS = ("step_mfu_pct.ouro", "ouro_decode_roofline",
                "loop_attn_roofline", "loop_mlp_roofline",
                "loop_attn_share_pct", "loop_norm_share_pct")


@pytest.fixture(scope="module")
def ouro():
    return registry.load_json("configs", "ouro-2.6b-serve")


def test_the_cell_resolves_every_file_and_reader_it_names(ouro):
    cell = registry.resolve(CELL)
    assert cell["listed"] and cell["chips"] == 1
    assert cell["config_file"] == ouro and ouro["driver"] == "looped"
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "drivers", ouro["driver"] + ".py"))
    assert cell["end_to_end"] == ["itl_p95_ms", "serve_tokens_per_s",
                                  "setup_s"]
    assert set(cell["per_layer"]) == set(LOOP_READERS) | {
        "batch_occupancy_pct", "decode_step_ms", "device_idle_pct.serve"}
    for name in cell["per_layer"]:
        assert callable(registry.metric_reader(name))
    # No other cell lists a loop reader.
    for m in registry.benchmark_json()["per_layer"]:
        if m["name"] in LOOP_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    engine = ouro["engine"]
    assert (engine["batch_size"], engine["kv_layout"],
            engine["decode_chunk"]) == (4, "dense", 1)
    assert engine["max_seq_len"] + engine["max_new_tokens"] >= 1024
    assert ouro["limits"]["unanswered"] == 0
    assert not registry.resolve(REHEARSAL)["listed"]


def test_the_configuration_is_the_published_one_uncut(ouro):
    assert ouro["reduced"] == [] and ouro["source"].endswith(
        "ByteDance/Ouro-2.6B/blob/main/config.json")
    published = {"head_dim": 128, "hidden_size": 2048,
                 "intermediate_size": 5632, "num_attention_heads": 16,
                 "num_hidden_layers": 48, "num_key_value_heads": 16,
                 "rms_norm_eps": 1e-06, "rope_theta": 1000000,
                 "vocab_size": 49152, "total_ut_steps": 4,
                 "early_exit_threshold": 1, "max_position_embeddings": 65536,
                 "tie_word_embeddings": False, "max_window_layers": 48}
    assert {k: ouro[k] for k in published} == published
    assert ouro["layer_types"] == ["full_attention"] * 48
    assert {"norms", "loop", "cache", "exit_gate", "weights"} \
        <= set(ouro["assumed"])


def test_reason_decode_is_one_session_a_slot_whatever_the_seed(ouro):
    mix = registry.load_json("traffic", "reason-decode")
    assert mix["judged_tokens"] == 512
    runs = [traffic.serve_schedule(mix, seed, 1.0, ouro["vocab_size"])
            for seed in (1, 2_999_999_999)]
    for schedule in runs:
        assert len(schedule) == ouro["engine"]["batch_size"] == 4
        assert all(r["due"] == 0.0 and r["max_new_tokens"] == 1024
                   for r in schedule)
    lengths = [[len(r["tokens"]) for r in s] for s in runs]
    assert lengths[0] == lengths[1] and sorted(lengths[0]) == [72, 109, 150,
                                                               228]
    assert max(lengths[0]) <= ouro["engine"]["max_seq_len"]
    assert runs[0][0]["tokens"] != runs[1][0]["tokens"]


def test_loop_counts_against_hand_worked_values(ouro):
    assert loop.attn_params(ouro) == 48 * 4 * 2048 * 2048 == 805_306_368
    assert loop.mlp_params(ouro) == 48 * 3 * 2048 * 5632 == 1_660_944_384
    assert loop.head_params(ouro) == 100_663_296
    assert loop.kv_bytes_per_token(ouro) == 1_572_864
    # ISSUE 36: the layers' matrices once a pass 4 x 4.93 GB, the head 0.20.
    assert loop.decode_bytes(ouro, 1, 0) == 19_931_332_608
    assert loop.mlp_bytes(ouro, 1) == 4 * 2 * 1_660_944_384
    # One step of 4 rows of 640 tokens: K/V of 2,560 attended tokens.
    assert loop.attn_bytes(ouro, 1, 2560) == (
        4 * 2 * 805_306_368 + 2560 * 1_572_864)
    assert loop.decode_flops(ouro, 4, 2560) == (
        2.0 * 4 * (4 * 2_466_250_752 + 100_663_296)
        + 4.0 * 2048 * 192 * 2560)


def _run(ouro, marks):
    return {"kind": "serve", "config": ouro, "slots": 4,
            "device": {"kind": "TPU v5 lite"}, "counters": marks,
            "trace_counters": marks,
            "trace": {"busy_s": 4.0, "window_s": 4.0,
                      "module_s": {"jit_decode_step": 3.9},
                      "module_n": {"jit_decode_step": 90}}}


def test_every_loop_reader_on_a_synthetic_trace(ouro, monkeypatch):
    marks = {"decode_steps": 90, "tokens_emitted": 360, "loop_passes": 360,
             "kv_tokens_attended": 360 * 600}
    reduced = {"scope_s": {"decode/attn": 2.0, "decode/mlp": 1.6,
                           "decode/loop_norm": 0.02, "decode/post_norm": 0.06,
                           "decode/head": 0.1, "prefill/attn": 9.0}}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "synthetic")
    monkeypatch.setattr(loop, "_load", lambda path: reduced)
    got = registry.read_metrics(list(LOOP_READERS), _run(ouro, marks))
    assert set(got) == set(LOOP_READERS)
    assert got["loop_attn_share_pct"] == pytest.approx(50.0)  # not prefill
    assert got["loop_norm_share_pct"] == pytest.approx(2.0)
    assert got["loop_mlp_roofline"] == pytest.approx(
        100 * loop.mlp_bytes(ouro, 90) / 819e9 / 1.6)
    assert got["loop_attn_roofline"] == pytest.approx(
        100 * loop.attn_bytes(ouro, 90, 216000) / 819e9 / 2.0)
    assert got["ouro_decode_roofline"] == pytest.approx(
        100 * loop.decode_bytes(ouro, 90, 216000) / 819e9 / 3.9)
    assert got["step_mfu_pct.ouro"] == pytest.approx(
        100 * loop.decode_flops(ouro, 360, 216000) / 197e12 / 3.9)
    assert all(0 < v <= 100 for v in got.values())


def test_loop_readers_are_silent_without_their_names(ouro, monkeypatch):
    """An untraced run, a decoder from before the loop's counters, a trace
    whose program set none of the scopes, a configuration that does not
    loop: nothing on the line, nothing raised."""
    old = {"decode_steps": 90, "tokens_emitted": 360}
    new = {**old, "loop_passes": 360, "kv_tokens_attended": 216000}
    untraced = {**_run(ouro, new), "trace": None, "trace_counters": None}
    assert registry.read_metrics(list(LOOP_READERS), untraced) == {}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "synthetic")
    monkeypatch.setattr(loop, "_load",
                        lambda path: {"scope_s": {"decode/head": 1.0}})
    assert registry.read_metrics(list(LOOP_READERS), _run(ouro, old)) == {}
    assert set(registry.read_metrics(list(LOOP_READERS), _run(ouro, new))) \
        == {"step_mfu_pct.ouro", "ouro_decode_roofline"}
    mistral = registry.load_json("configs", "mistral-7b-v0.3-serve")
    assert registry.read_metrics(list(LOOP_READERS),
                                 _run(mistral, new)) == {}


def test_the_loop_scopes_are_the_programs_in_a_spans_module_of_loops_own():
    from kubeflow_tpu.observability import tracing

    assert loop.LOOP_SCOPES == tracing.LOOP_SCOPES
    assert not set(loop.LOOP_SCOPES) & set(tracing.DEVICE_SCOPES)
    own = loop._loop_spans()
    assert own is not spans and own is loop._loop_spans()
    assert own.DEVICE_SCOPES == spans.DEVICE_SCOPES + loop.LOOP_SCOPES
    op_name = "jit(decode_step)/decode/while/body/post_norm/mul"
    assert own.scope_path(op_name) == ("decode", "post_norm")
    assert spans.scope_path(op_name) == ("decode",)


@pytest.fixture(scope="module")
def looped_rehearsal():
    """One run of the rehearsal cell on the CPU through run.py's own
    main(), with the control readings: (rc, every JSON line of stdout)."""
    import contextlib
    import io

    from benchmarks import run as bench_run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", REHEARSAL, "--seed", "3000000001",
                             "--seconds", "3", "--trace", "1",
                             "--control", "1"])
    return rc, [json.loads(line) for line in out.getvalue().splitlines()
                if line.startswith("{")]


def test_the_looped_rehearsal_prints_a_correct_line(looped_rehearsal):
    rc, lines = looped_rehearsal
    assert rc == 0
    earlier, last = lines[0], lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 4 == earlier["streaming_at_close"]
    assert earlier["compiles_in_window"] == 0 and not earlier["errors"]
    assert earlier["cache_layers"] == 12
    assert earlier["kv_bytes_per_token"] == 2 * 12 * 4 * 16 * 4
    # Four sessions, the first 24 tokens each was served in the window.
    assert earlier["judged_tokens"] == 96
    c = earlier["counters"]
    assert c["prefill_tokens"] == 0 == c["requests_admitted"]
    assert c["loop_passes"] == 4 * c["decode_steps"] > 0
    assert c["kv_tokens_attended"] > c["tokens_emitted"] > 0
    assert last["metrics"]["batch_occupancy_pct"]["value"] > 90
    assert last["checks"]["served_logit_gap"]["value"] <= 0.001
    assert last["checks"]["unanswered"] == {"value": 0.0, "limit": 0}


@pytest.mark.parametrize("fault", ["int8", "three_passes", "shared_cache",
                                   "no_loop_norm"])
def test_a_planted_loop_fault_reads_over_the_limit(looped_rehearsal, fault):
    _, lines = looped_rehearsal
    control = next(line for line in lines if "control" in line)
    limit = registry.load_json("configs", "rehearsal-tiny-ouro")["limits"]
    assert control["control"][fault]["widest_gap"] \
        > limit["served_logit_gap"] >= control["program"]["served_logit_gap"]


def test_a_program_that_cannot_loop_fails_at_once(monkeypatch):
    """What the parent commit does on the new cell: its TransformerConfig
    has no ``n_passes``, and the driver says so and exits."""
    import dataclasses

    from benchmarks.drivers import looped
    from kubeflow_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class Parent:
        vocab_size: int = 0

    monkeypatch.setattr(transformer, "TransformerConfig", Parent)
    with pytest.raises(SystemExit, match="cannot hold rehearsal-tiny-ouro"
                                         ".*unexpected keyword"):
        looped.register_preset(registry.load_json("configs",
                                                  "rehearsal-tiny-ouro"))
