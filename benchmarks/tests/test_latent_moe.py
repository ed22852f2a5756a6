"""CPU tests of what the latent-attention expert driver and its counts add
to the benchmark: the new cell's entry, files and traffic, the counts
against hand-worked values at the published widths, every new reader on a
synthetic trace (and silent where a program sets none of its names), and
the driver on the rehearsal configuration, ``correct`` as the program
stands and over the limit with each planted fault.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_latent_moe.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import latent_moe, registry, spans, traffic  # noqa: E402

CELL = "kanana-2-30b-a3b-serve.moe-decode"
REHEARSAL = "rehearsal-tiny-kanana.moe-decode-tiny"
READERS = ("step_mfu_pct.kanana", "kanana_decode_roofline",
           "moe_experts_roofline", "latent_attn_roofline",
           "moe_route_share_pct", "experts_touched_pct")


@pytest.fixture(scope="module")
def kanana():
    return registry.load_json("configs", "kanana-2-30b-a3b-serve")


def test_the_new_cell_resolves_every_file_and_reader_it_names(kanana):
    cell = registry.resolve(CELL)
    assert cell["listed"] and cell["chips"] == 1
    assert cell["config_file"] == kanana and kanana["driver"] == "latent_moe"
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "drivers", kanana["driver"] + ".py"))
    assert cell["end_to_end"] == ["itl_p95_ms", "serve_tokens_per_s",
                                  "setup_s"]
    assert set(cell["per_layer"]) == set(READERS) | {
        "batch_occupancy_pct", "decode_step_ms", "device_idle_pct.serve"}
    for name in cell["per_layer"]:
        assert callable(registry.metric_reader(name))
    for m in registry.benchmark_json()["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
    engine = kanana["engine"]
    assert (engine["batch_size"], engine["kv_layout"], engine["decode_chunk"],
            engine["max_seq_len"] + engine["max_new_tokens"]) == (
        64, "dense", 1, 8192)
    assert kanana["limits"]["unanswered"] == 0
    assert set(kanana["limits"]) == {"served_logit_gap",
                                     "served_mismatch_pct", "unanswered"}
    assert not registry.resolve(REHEARSAL)["listed"]


def test_the_configuration_is_the_catalogs_but_its_depth(kanana):
    """Every number of the catalog's row as published, but the depth."""
    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 32,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 128256, "q_lora_rank": None, "rope_scaling": None,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "rope_interleave": True,
        "tie_word_embeddings": False}
    assert {k: kanana[k] for k in published} == published
    assert kanana["num_hidden_layers"] == 6
    assert kanana["reduced"] == ["num_hidden_layers"]
    assert kanana["source"].endswith(
        "kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json")
    assert {"torch_dtype", "selection_bias", "weights", "rotary"} \
        <= set(kanana["assumed"])
    assert "8-stage pipeline" in kanana["deployment"]


def test_moe_decode_is_one_session_a_slot_whatever_the_seed(kanana):
    mix = registry.load_json("traffic", "moe-decode")
    assert (mix["judged_tokens"], mix["judged_sessions"]) == (256, 4)
    runs = [traffic.serve_schedule(mix, seed, 1.0, kanana["vocab_size"])
            for seed in (1, 2_999_999_999)]
    for schedule in runs:
        assert len(schedule) == kanana["engine"]["batch_size"] == 64
        assert all(r["due"] == 0.0 and r["max_new_tokens"] == 6144
                   for r in schedule)
    lengths = [[len(r["tokens"]) for r in s] for s in runs]
    assert lengths[0] == lengths[1]
    assert min(lengths[0]) == 512 and max(lengths[0]) == 2048
    assert sorted(lengths[0])[32] == 1034 and sum(lengths[0]) == 71_693
    assert runs[0][0]["tokens"] != runs[1][0]["tokens"]


def test_latent_counts_against_hand_worked_values(kanana):
    assert latent_moe.attn_params(kanana) == (
        2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048) == 26_345_472
    assert latent_moe.expert_params(kanana) == 4_718_592
    assert latent_moe.shared_params(kanana) == 9_437_184
    assert latent_moe.dense_mlp_params(kanana) == 37_748_736
    assert latent_moe.head_params(kanana) == 262_668_288
    assert latent_moe.kv_bytes_per_token(kanana) == 6_912
    assert latent_moe.expert_layers(kanana) == 5
    # Every non-expert matrix once, head included: 1.01 GB.
    assert latent_moe.fixed_bytes(kanana) == (
        2 * (6 * 26_345_472 + 37_748_736 + 5 * 9_437_184 + 262_668_288)
        + 5 * 2049 * 128 * 4)
    # A step that touched 122 experts in each expert layer, rows 2,300 long.
    assert latent_moe.decode_bytes(kanana, 1, 610, 64 * 2300) == (
        latent_moe.fixed_bytes(kanana) + 2 * 610 * 4_718_592
        + 6_912 * 64 * 2300)
    assert latent_moe.expert_bytes(kanana, 1, 610) == 2 * (
        610 * 4_718_592 + 5 * 9_437_184)
    assert latent_moe.decode_flops(kanana, 64, 64 * 2300) == (
        2.0 * 64 * (6 * 26_345_472 + 37_748_736
                    + 5 * (2048 * 128 + 9_437_184 + 6 * 4_718_592)
                    + 262_668_288)
        + 2.0 * 32 * (2 * 512 + 64) * 6 * 64 * 2300)


def _run(cfg, marks):
    return {"kind": "serve", "config": cfg, "slots": 64,
            "device": {"kind": "TPU v5 lite"}, "counters": marks,
            "trace_counters": marks,
            "trace": {"busy_s": 4.0, "window_s": 4.0,
                      "module_s": {"jit_decode_step": 3.9},
                      "module_n": {"jit_decode_step": 300}}}


MARKS = {"decode_steps": 300, "tokens_emitted": 19_200,
         "kv_tokens_attended": 19_200 * 2_300,
         "experts_touched": 300 * 5 * 122}


def test_every_latent_reader_on_a_synthetic_trace(kanana, monkeypatch):
    reduced = {"scope_s": {"decode/attn/latent_attn": 1.0,
                           "decode/attn": 0.5, "decode/mlp/moe_route": 0.2,
                           "decode/mlp/moe_experts": 2.5, "decode/head": 0.1,
                           "prefill/mlp/moe_experts": 9.0},
               # The grouped products' kernels carry no scope path.
               "op_s": {((), "ragged-dot-none.1"): 1.0,
                        ((), "ragged-dot-metadata"): 0.5,
                        ((), "copy-start.3"): 7.0,
                        (("decode", "mlp", "moe_experts"), "fusion.9"): 2.5}}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "synthetic")
    monkeypatch.setattr(latent_moe, "_load", lambda path: reduced)
    got = registry.read_metrics(list(READERS), _run(kanana, MARKS))
    assert set(got) == set(READERS)
    assert got["moe_route_share_pct"] == pytest.approx(5.0)  # not prefill
    assert got["experts_touched_pct"] == pytest.approx(100 * 122 / 128)
    assert got["moe_experts_roofline"] == pytest.approx(
        100 * latent_moe.expert_bytes(kanana, 300, 300 * 5 * 122)
        / 819e9 / (2.5 + 1.0 + 0.5))
    assert got["latent_attn_roofline"] == pytest.approx(
        100 * 6_912 * 19_200 * 2_300 / 819e9 / 1.0)
    assert got["kanana_decode_roofline"] == pytest.approx(
        100 * latent_moe.decode_bytes(kanana, 300, 300 * 5 * 122,
                                      19_200 * 2_300) / 819e9 / 3.9)
    assert got["step_mfu_pct.kanana"] == pytest.approx(
        100 * latent_moe.decode_flops(kanana, 19_200, 19_200 * 2_300)
        / 197e12 / 3.9)
    assert all(0 < v <= 100 for v in got.values())


def test_the_new_readers_are_silent_without_their_names(kanana, monkeypatch):
    """An untraced run, a decoder from before the routing counters, a
    trace whose program set none of the scopes, a configuration without
    latent attention: nothing on the line, nothing raised."""
    old = {k: v for k, v in MARKS.items() if k != "experts_touched"}
    untraced = {**_run(kanana, MARKS), "trace": None, "trace_counters": None}
    assert set(registry.read_metrics(list(READERS), untraced)) == {
        "experts_touched_pct"}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "synthetic")
    monkeypatch.setattr(latent_moe, "_load",
                        lambda path: {"scope_s": {"decode/head": 1.0}})
    assert registry.read_metrics(list(READERS), _run(kanana, old)) == {}
    assert set(registry.read_metrics(list(READERS), _run(kanana, MARKS))) \
        == {"step_mfu_pct.kanana", "kanana_decode_roofline",
            "experts_touched_pct"}
    ouro = registry.load_json("configs", "ouro-2.6b-serve")
    assert registry.read_metrics(list(READERS), _run(ouro, MARKS)) == {}


def test_the_scopes_are_the_programs_in_a_spans_module_of_their_own():
    from kubeflow_tpu.observability import tracing

    assert latent_moe.LATENT_MOE_SCOPES == tracing.LATENT_MOE_SCOPES
    assert not set(latent_moe.LATENT_MOE_SCOPES) & set(tracing.DEVICE_SCOPES)
    own = latent_moe._latent_spans()
    assert own is not spans and own is latent_moe._latent_spans()
    assert own.DEVICE_SCOPES == spans.DEVICE_SCOPES \
        + latent_moe.LATENT_MOE_SCOPES
    op_name = "jit(decode_step)/decode/while/body/mlp/moe_experts/dot"
    assert own.scope_path(op_name) == ("decode", "mlp", "moe_experts")
    assert spans.scope_path(op_name) == ("decode", "mlp")


@pytest.fixture(scope="module")
def kanana_rehearsal():
    """One run of the rehearsal cell on the CPU through run.py's own
    main(), with the control readings: (rc, every JSON line of stdout)."""
    import contextlib
    import io

    from benchmarks import run as bench_run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", REHEARSAL, "--seed", "4000000001",
                             "--seconds", "3", "--trace", "1",
                             "--control", "1"])
    return rc, [json.loads(line) for line in out.getvalue().splitlines()
                if line.startswith("{")]


def test_the_latent_rehearsal_prints_a_correct_line(kanana_rehearsal):
    rc, lines = kanana_rehearsal
    assert rc == 0
    earlier, last = lines[0], lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 4 == earlier["streaming_at_close"]
    assert earlier["compiles_in_window"] == 0 and not earlier["errors"]
    assert earlier["cache_layers"] == 3
    assert earlier["kv_bytes_per_token"] == 3 * 48 * 4
    assert earlier["dense_attn_impl"] == "xla"
    assert len(earlier["expert_load_max_over_mean"]) == 2
    assert 1.0 <= earlier["experts_touched_per_layer_step"] <= 8
    assert earlier["judged_tokens"] == 96  # 4 sessions x 24
    c = earlier["counters"]
    assert c["prefill_tokens"] == 0 == c["requests_admitted"]
    # Two choices in each of two expert layers a token; the device's count
    # is read with the step in flight (one ahead of the host's routing),
    # so the two differences may part by up to a step at each end.
    assert abs(c["tokens_routed"] - 2 * 2 * c["tokens_emitted"]) \
        <= 2 * 4 * 2 * 2 and c["tokens_routed"] > 0
    assert c["kv_tokens_attended"] > c["tokens_emitted"] > 0
    assert last["metrics"]["batch_occupancy_pct"]["value"] > 90
    assert 0 < last["metrics"]["experts_touched_pct"]["value"] <= 100
    assert last["checks"]["served_logit_gap"]["value"] <= 0.001
    assert last["checks"]["served_mismatch_pct"]["value"] == 0.0
    assert last["checks"]["unanswered"] == {"value": 0.0, "limit": 0}


@pytest.mark.parametrize("fault", ["int8", "softmax_routing",
                                   "no_routed_scaling", "no_shared",
                                   "capacity", "no_kv_norm"])
def test_a_planted_latent_fault_reads_over_the_limit(kanana_rehearsal,
                                                    fault):
    _, lines = kanana_rehearsal
    control = next(line for line in lines if "control" in line)
    limit = registry.load_json("configs", "rehearsal-tiny-kanana")["limits"]
    program, read = control["program"], control["control"][fault]
    assert limit["served_logit_gap"] >= program["served_logit_gap"]
    assert limit["served_mismatch_pct"] >= program["served_mismatch_pct"]
    assert read["widest_gap"] > limit["served_logit_gap"] \
        or read["mismatch_pct"] > limit["served_mismatch_pct"]


def test_a_program_without_latent_attention_fails_at_once(monkeypatch):
    """What the parent commit does on the new cell: its TransformerConfig
    has no ``kv_lora_rank``, and the driver says so and exits."""
    import dataclasses

    from benchmarks.drivers import latent_moe as driver
    from kubeflow_tpu.models import transformer

    @dataclasses.dataclass(frozen=True)
    class Parent:
        vocab_size: int = 0

    monkeypatch.setattr(transformer, "TransformerConfig", Parent)
    with pytest.raises(SystemExit, match="cannot hold rehearsal-tiny-kanana"
                                         ".*unexpected keyword"):
        driver.register_preset(registry.load_json("configs",
                                                  "rehearsal-tiny-kanana"))
