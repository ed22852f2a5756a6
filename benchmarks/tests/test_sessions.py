"""CPU tests of what the sessions driver and the ``mixer_types`` counts
add to the benchmark: the new cells' entries and traffic, the counts
against hand-worked values, every new reader on a synthetic trace (and
silent where a program sets none of its names), and the driver on the
rehearsal configuration, ``correct`` as the program stands and over the
limit with each cache fault planted.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_sessions.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import hybrid, registry, spans, traffic  # noqa: E402

LONG_DECODE = "minicpm-sala-serve.long-decode"
BACKLOG = "mistral-7b-v0.3-serve.batch-backlog-1200"
NEW_READERS = ("step_mfu_pct.sala", "sala_decode_roofline",
               "linear_attn_roofline", "sparse_attn_roofline",
               "linear_attn_share_pct", "sparse_attn_share_pct",
               "sparse_read_share_pct")


@pytest.fixture(scope="module")
def sala():
    return registry.load_json("configs", "minicpm-sala-serve")


def test_the_new_cells_resolve_and_report_what_the_issue_names():
    cell = registry.resolve(LONG_DECODE)
    assert cell["listed"] and cell["chips"] == 1
    assert cell["end_to_end"] == ["itl_p95_ms", "serve_tokens_per_s",
                                  "setup_s"]
    assert set(NEW_READERS) <= set(cell["per_layer"])
    # Of the readers that were there, the three that read something in
    # it and are free to list it; test_spans.py holds PR 26's seven (the
    # idle split, the scheduler's host time, the cast share) to one cell
    # each, and idle_in_host_pct.serve raises in a window without
    # admissions.
    assert set(cell["per_layer"]) - set(NEW_READERS) == {
        "batch_occupancy_pct", "decode_step_ms", "device_idle_pct.serve"}
    # The issue's engine: one step a dispatch, 4,608 tokens a session.
    engine = cell["config_file"]["engine"]
    assert engine["decode_chunk"] == 1 and engine["max_new_tokens"] == 4608
    # The backlog runs as the old batch-backlog file does, unlisted: its
    # tokens per second spread 2.2 % over six runs on the chip, four times
    # what half the metric's bound admits (PERF.md, PR 28).
    backlog = registry.resolve(BACKLOG)
    assert not backlog["listed"]
    assert backlog["config_file"]["name"] == "mistral-7b-v0.3-serve"


def test_the_configuration_keeps_every_published_number(sala):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert sala["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in sala["reduced"]:
            assert sala["published"][key] == value
        else:
            assert sala[key] == value, key
    assert sala["mixer_types"] == row["config"]["mixer_types"][::4]
    assert sala["num_hidden_layers"] == len(sala["mixer_types"]) == 8


def test_long_decode_is_one_session_a_slot_whatever_the_seed(sala):
    mix = registry.load_json("traffic", "long-decode")
    a = traffic.serve_schedule(mix, 1, 1.0, sala["vocab_size"])
    b = traffic.serve_schedule(mix, 2, 1.0, sala["vocab_size"])
    assert len(a) == sala["engine"]["batch_size"] == 64
    lengths = [len(r["tokens"]) for r in a]
    assert lengths == [len(r["tokens"]) for r in b]
    assert a[0]["tokens"] != b[0]["tokens"]
    assert min(lengths) == 4096 and max(lengths) == 32768
    assert sum(lengths) == 880057
    assert sum(n <= sala["sparse_config"]["dense_len"] for n in lengths) == 13
    assert {r["max_new_tokens"] for r in a} == {4608}
    assert all(r["due"] == 0.0 for r in a)
    # Every session's worst case fits the pool, all at once.
    eng = sala["engine"]
    assert sum(-(-(n + 4608) // eng["kv_block_size"]) for n in lengths) \
        <= eng["kv_pool_blocks"]
    assert max(lengths) + 4608 == eng["max_prompt_len"] + eng["max_new_tokens"]


def test_batch_backlog_1200_outlasts_the_window():
    mix = registry.load_json("traffic", "batch-backlog-1200")
    old = registry.load_json("traffic", "batch-backlog")
    assert {k: v for k, v in mix.items() if k not in ("rate_per_s", "note")} \
        == {k: v for k, v in old.items() if k not in ("rate_per_s", "note")}
    schedule = traffic.serve_schedule(mix, 1, 30.0, 32768)
    assert len(schedule) == 1200 and all(r["due"] == 0.0 for r in schedule)


def test_counts_against_hand_worked_values(sala):
    # 6 x (5 x 4096^2 + 3 x 4096 x 16384) + 2 x (3 x 4096^2
    # + 2 x 4096 x 256 + 3 x 4096 x 16384) + 4096 x 73448
    assert hybrid.matmul_params(sala) == 6 * 285212672 + 2 * 253755392 \
        + 300843008 == 2519629824
    counters = {"rows_dense": 10, "rows_sparse": 54,
                "sparse_tokens_attended": 10 * 7000 + 54 * 4064.5,
                "sparse_tokens_in_context": 10 * 7000 + 54 * 16000}
    n = hybrid.row_steps(counters, sala)
    assert n["rows"] == 64 and n["windows"] == pytest.approx(54 * 16000 / 16)
    # Per row and lightning layer 2 MiB of state, read and written.
    assert hybrid.linear_attn_bytes(sala, n) == 6 * 64 * 2 * 2 ** 21
    # 1 KiB a token a sparse layer for K and V, half that a compressed key.
    assert hybrid.sparse_attn_bytes(sala, n) == pytest.approx(
        2 * (1024 * n["attended"] + 512 * n["windows"]))
    assert hybrid.decode_bytes(sala, 1, n) == pytest.approx(
        2 * 2519629824 + 6 * 64 * 2 ** 22
        + hybrid.sparse_attn_bytes(sala, n))
    assert hybrid.decode_flops(sala, n) == pytest.approx(
        2 * 2519629824 * 64 + 6 * 64 * 5 * 4096 * 128
        + 2 * (4 * 4096 * n["attended"] + 2 * 4096 * n["windows"]))


def _run(sala, marks):
    return {"kind": "serve", "config": sala, "counters": marks,
            "trace_counters": marks, "slots": 64,
            "device": {"kind": "TPU v5e"},
            "trace": {"busy_s": 2.0, "window_s": 2.5,
                      "module_s": {"jit_decode_step": 1.8},
                      "module_n": {"jit_decode_step": 100}}}


def test_every_new_reader_on_a_synthetic_trace(sala, monkeypatch):
    marks = {"decode_steps": 100, "rows_dense": 1000, "rows_sparse": 5400,
             "sparse_tokens_attended": 1000 * 7000 + 5400 * 4064.5,
             "sparse_tokens_in_context": 1000 * 7000 + 5400 * 16000,
             "tokens_emitted": 6400}
    run = _run(sala, marks)
    reduced = {"scope_s": {"decode/attn/linear_attn": 0.4,
                           "decode/attn/sparse_select": 0.1,
                           "decode/attn/sparse_attn": 0.5,
                           "prefill/attn/sparse_attn": 9.0,
                           "decode/mlp": 0.6}}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "synthetic")
    monkeypatch.setattr(hybrid, "_load", lambda path: reduced)
    n = hybrid.row_steps(marks, sala)
    got = registry.read_metrics(list(NEW_READERS), run)
    assert set(got) == set(NEW_READERS)
    assert got["linear_attn_share_pct"] == pytest.approx(20.0)
    assert got["sparse_attn_share_pct"] == pytest.approx(30.0)  # not prefill
    assert got["linear_attn_roofline"] == pytest.approx(
        100 * hybrid.linear_attn_bytes(sala, n) / 819e9 / 0.4)
    assert got["sparse_attn_roofline"] == pytest.approx(
        100 * hybrid.sparse_attn_bytes(sala, n) / 819e9 / 0.6)
    assert got["sala_decode_roofline"] == pytest.approx(
        100 * hybrid.decode_bytes(sala, 100, n) / 819e9 / 1.8)
    assert got["step_mfu_pct.sala"] == pytest.approx(
        100 * hybrid.decode_flops(sala, n) / 197e12 / 1.8)
    assert got["sparse_read_share_pct"] == pytest.approx(
        100 * marks["sparse_tokens_attended"]
        / marks["sparse_tokens_in_context"])
    assert all(0 < v <= 100 for v in got.values())


def test_new_readers_are_silent_without_their_names(sala, monkeypatch):
    """An untraced run, a decoder that counts none of the new counters (a
    commit from before them), a trace whose program set none of the
    scopes: nothing on the line, nothing raised."""
    marks = {"decode_steps": 100, "tokens_emitted": 6400}
    untraced = {**_run(sala, marks), "trace": None, "trace_counters": None}
    assert registry.read_metrics(list(NEW_READERS), untraced) == {}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "synthetic")
    monkeypatch.setattr(hybrid, "_load",
                        lambda path: {"scope_s": {"decode/attn": 1.0}})
    assert registry.read_metrics(list(NEW_READERS), _run(sala, marks)) == {}
    mistral = registry.load_json("configs", "mistral-7b-v0.3-serve")
    assert registry.read_metrics(list(NEW_READERS),
                                 _run(mistral, marks)) == {}


def test_the_mixer_scopes_are_the_programs():
    from kubeflow_tpu.observability import tracing

    assert hybrid.MIXER_SCOPES == tracing.MIXER_SCOPES
    assert not set(hybrid.MIXER_SCOPES) & set(spans.DEVICE_SCOPES)


def test_the_mixers_names_live_in_a_spans_module_of_hybrids_own():
    """The mixer readers reduce a trace with ``spans.py`` loaded a second
    time; the module the accepted readers import never learns the names."""
    own = hybrid._mixer_spans()
    assert own is not spans and own is hybrid._mixer_spans()
    assert own.DEVICE_SCOPES == spans.DEVICE_SCOPES + hybrid.MIXER_SCOPES
    op_name = "jit(f)/decode/attn/linear_attn/mul"
    assert own.scope_path(op_name) == ("decode", "attn", "linear_attn")
    assert spans.scope_path(op_name) == ("decode", "attn")

    def ins(name, id_, opcode, operands=(), op_name=""):
        return {"name": name, "id": id_, "opcode": opcode, "op_name": op_name,
                "operands": list(operands), "called": [], "comp": 1,
                "tuple_index": 0, "parameter_number": 0, "root": False,
                "entry": True}

    # A copy the compiler made up is booked to what consumes it, down to
    # the mixer's scope here and to ``attn`` there.
    graph = [ins("pool", 1, "parameter", op_name="state['pool']['k']"),
             ins("copy.1", 2, "copy", [1]),
             ins("gather.1", 3, "gather", [2],
                 "jit(f)/decode/attn/sparse_attn/gather")]
    assert own.resolve_paths(graph)["copy.1"] == (
        "decode", "attn", "sparse_attn")
    assert spans.resolve_paths(graph)["copy.1"] == ("decode", "attn")


def test_a_stalled_stream_is_slow_and_a_dead_one_is_unanswered():
    """At the close one stream is mid-stall and resumes, one has finished,
    one ended without finishing, one never resumes: the first two are
    still streaming, and the wait ends with the patience."""
    import threading
    import time
    import types

    from benchmarks.drivers import sessions as driver

    t_close = time.perf_counter()

    def stream(**kw):
        return types.SimpleNamespace(stamps=[t_close - 1.5], finished=False,
                                     is_alive=lambda: True, **kw)

    stalled, dead = stream(), stream()
    done = stream()
    done.finished = True
    ended = stream()
    ended.is_alive = lambda: False
    threading.Timer(0.1, lambda: stalled.stamps.append(
        time.perf_counter())).start()
    answered = driver.still_streaming([stalled, done, ended, dead], t_close,
                                      patience=0.4)
    assert answered == [stalled, done]
    assert 0.4 <= time.perf_counter() - t_close < 1.0


def test_slow_rounds_are_the_gaps_well_over_the_median():
    """Ten rounds of 20 ms and one of 60 inside the window, one gap that
    straddles its start: 40 ms over the median in one slow round."""
    from benchmarks.drivers import sessions as driver

    stamps = [0.99]
    for gap in [0.02] * 5 + [0.06] + [0.02] * 5:
        stamps.append(stamps[-1] + gap)
    read = driver.slow_rounds(stamps, 1.0, 2.0)
    assert read["slow_rounds"] == 1
    assert read["slow_rounds_s"] == pytest.approx(0.04)
    assert read["itl_max_ms"] == pytest.approx(60.0)
    assert driver.slow_rounds(stamps, 5.0, 6.0) == {
        "itl_max_ms": None, "slow_rounds": 0, "slow_rounds_s": 0.0}


def test_the_collectors_passes_are_counted_by_generation():
    import gc

    from benchmarks.drivers import sessions as driver

    watch = driver._GcWatch()
    gc.callbacks.append(watch)
    try:
        gc.collect(0)
        gc.collect(2)
    finally:
        gc.callbacks.remove(watch)
    assert watch.passes[0] >= 1 and watch.passes[2] >= 1
    assert 0.0 < watch.longest_s <= watch.pause_s


@pytest.fixture(scope="module")
def rehearsal():
    """One run of the rehearsal cell on the CPU through run.py's own
    main(), with the control readings: (rc, every JSON line of stdout)."""
    import contextlib
    import io

    from benchmarks import run as bench_run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload",
                             "rehearsal-tiny-sala.long-decode-tiny",
                             "--seed", "1", "--seconds", "3", "--trace", "1",
                             "--control", "1"])
    return rc, [json.loads(line) for line in out.getvalue().splitlines()
                if line.startswith("{")]


def test_the_rehearsal_cell_prints_a_correct_line(rehearsal):
    rc, lines = rehearsal
    assert rc == 0
    earlier, last = lines[0], lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 4 == earlier["streaming_at_close"]
    assert earlier["compiles_in_window"] == 0 and not earlier["errors"]
    assert earlier["state_bytes"] > 0
    assert earlier["setup_prefill_tokens_per_s"] > 0
    assert len(earlier["gc_passes"]) == 3 and earlier["slow_rounds_s"] >= 0
    c = earlier["counters"]
    assert c["prefill_tokens"] == 0 == c["requests_admitted"]
    assert c["rows_dense"] + c["rows_sparse"] == c["tokens_emitted"] > 0
    assert 0 < last["metrics"]["sparse_read_share_pct"]["value"] < 100
    assert last["checks"]["served_logit_gap"]["value"] <= 0.001
    assert last["checks"]["unanswered"] == {"value": 0.0, "limit": 0}


@pytest.mark.parametrize("fault", ["no_selection", "state_dropped"])
def test_a_planted_cache_fault_reads_over_the_limit(rehearsal, fault):
    _, lines = rehearsal
    control = next(line for line in lines if "control" in line)
    limit = registry.load_json("configs", "rehearsal-tiny-sala")["limits"]
    assert control["control"][fault]["widest_gap"] \
        > limit["served_logit_gap"] >= control["program"]["served_logit_gap"]
