"""CPU tests of the benchmark's own code: the contract of BENCHMARK.json,
the generators and the arithmetic against hand-worked values, the trace
reduction on a hand-made event list, and both drivers on the rehearsal
configuration: ``correct`` true as the program stands, false with the
control (the reference in int8 in the program's place) and with each fault
planted under the harness.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import check, registry, stats, trace, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEED = 1
MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336, "head_dim": 128,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "vocab_size": 32768, "num_hidden_layers": 4}


@pytest.fixture(scope="module")
def bench():
    return registry.benchmark_json()


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    named = metrics + bench["configs"] + bench["workloads"]
    assert all(NAME.match(x["name"]) for x in named)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(0 < m["bound"] <= 0.1 for m in bench["end_to_end"])
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)


def test_every_moves_names_a_metric_its_cells_report(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m
    for cell in cells:
        reported = [n for n, at in e2e.items() if cell in at]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_every_cell_resolves_its_files_by_name(bench):
    for w in bench["workloads"]:
        cell = registry.resolve(w["name"])
        assert cell["listed"] and cell["config_file"]["name"] == w["config"]
        for name in cell["per_layer"]:
            assert callable(registry.metric_reader(name))
    for c in bench["configs"]:
        held = registry.load_json("configs", c["name"])
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert held["reduced"] == c["reduced"] and held["source"] == c["source"]
    # A workload with files but no entry runs too (the knee, the rehearsal).
    assert not registry.resolve("mistral-7b-v0.3-serve.batch-backlog")["listed"]


def test_generators_follow_the_seed_and_keep_the_work():
    mix = registry.load_json("traffic", "chat-steady")
    a, b, c = (traffic.serve_schedule(mix, s, 30, 32768) for s in (7, 7, 8))
    assert a == b and a != c
    shape = lambda s: [(r["due"], len(r["tokens"]), r["max_new_tokens"])  # noqa: E731
                       for r in s]
    # The mix states an order: every seed replays one schedule, and only
    # the token ids are the seed's. Without it the seed orders the work.
    assert shape(a) == shape(c)
    free = {k: v for k, v in mix.items() if k != "order_seed"}
    d, e = (traffic.serve_schedule(free, s, 30, 32768) for s in (7, 8))
    assert shape(d) != shape(e)
    assert sorted(x[1:] for x in shape(d)) != sorted(x[1:] for x in shape(e))
    assert collections.Counter(len(r["tokens"]) for r in d) == \
        collections.Counter(len(r["tokens"]) for r in a)
    assert len(a) == round(mix["rate_per_s"] * 30)
    assert max(r["due"] for r in a) < 30 and a[0]["due"] == 0.0
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert all(lo <= len(r["tokens"]) <= hi for r in a)
    train = registry.load_json("traffic", "stream-2k")
    x, y, z = (traffic.train_batches(train, s, 32768) for s in (7, 7, 8))
    assert all(np.array_equal(p, q) for p, q in zip(x, y))
    assert not np.array_equal(x[0], z[0]) and x[0].shape == (4, 2049)
    assert len({row.tobytes() for batch in x for row in batch}) == 32


def test_percentile_counts_the_missing_as_worst():
    assert stats.percentile([3.0, 1.0, 2.0, 4.0], 50) == 2.0
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile(list(range(1, 96)), 95, n_missing=5) == 95
    assert stats.percentile(list(range(1, 95)), 95, n_missing=6) == stats.MISSING
    assert stats.iqr_share([10, 10, 10, 10, 11, 9]) == pytest.approx(0.05)


def test_flop_and_byte_arithmetic_at_the_mistral_widths():
    # q,o 2 x 16.8 M; k,v 2 x 4.2 M; SwiGLU 3 x 58.7 M = 218.1 M a layer.
    assert stats.layer_params(MISTRAL) == 218_103_808
    assert stats.matmul_params(MISTRAL) == 4 * 218_103_808 + 134_217_728
    # 6 x 1.0066 B + 12 x 4 layers x 4096 x 2048 / 2 = 6.24 GFLOP a token.
    assert stats.train_flops_per_token(MISTRAL, 2048) == pytest.approx(
        6 * 1_006_632_960 + 201_326_592)
    # forward attention 4 T^2 hd / 2 per head, x 32 heads x 4 layers x 4
    # rows, backward twice that.
    assert stats.train_attention_flops(MISTRAL, 4, 2048) == pytest.approx(
        3 * 4 * 2048 * 2048 * 128 / 2 * 32 * 4 * 4)
    serve = {**MISTRAL, "num_hidden_layers": 6}
    assert stats.decode_step_bytes(serve, 0) == 2 * (6 * 218_103_808
                                                     + 134_217_728)
    assert stats.decode_step_bytes(serve, 1000) - stats.decode_step_bytes(
        serve, 0) == 1000 * 2 * 6 * 8 * 128 * 2
    assert stats.forward_flops(serve, 10, 0) == 20 * stats.matmul_params(serve)


def test_trace_reduction_on_a_hand_made_event_list():
    ops = [("%fusion.1 = f32[8] fusion(...)", 0.0, 1.0),
           ("%fusion.2 = f32[8] fusion(...)", 0.5, 1.5),   # overlaps the first
           ("%splash_fwd.3 = bf16[8] custom-call(%fusion.1)", 3.0, 4.0),
           ("%fusion.1 = f32[8] fusion(...)", 4.0, 4.5)]
    modules = [("jit_step(123)", 0.0, 1.5), ("jit_step(123)", 3.0, 4.5),
               ("jit_other(9)", 5.0, 5.0)]
    host = [("whole run", -1.0, 10.0), ("np.asarray(jax.Array)", 1.4, 3.1),
            ("short", 2.0, 2.1)]
    out = trace.reduce(ops, modules, host, window_s=5.0)
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["module_s"]["jit_step"] == pytest.approx(3.0)
    assert stats.module_time(out, r"^jit_step$") == (pytest.approx(3.0), 2)
    assert stats.op_time(out, "splash") == pytest.approx(1.0)
    assert stats.op_time(out, r"fusion\.1") == pytest.approx(1.5)
    # One gap, 1.5 -> 3.0; the event that covers all of it and is the
    # shorter of the two that do names it.
    assert out["breakdown"]["idle_gaps"] == [
        ["np.asarray(jax.Array)", pytest.approx(1.5)]]
    assert out["breakdown"]["device_ops"][0][1] == pytest.approx(1.5)
    assert trace.label_gaps([(0.0, 1.0)], []) == [("unattributed", 1.0)]


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-6}
    gap, leaf = check.worst_leaf_gap({"a": 10.5, "b": 1.0, "c": 2e-6}, ref)
    assert (gap, leaf) == (pytest.approx(0.05), "a")    # c: 1e-6 / median 1
    gap, leaf = check.worst_leaf_gap({"a": 10.0, "b": 0.0, "c": 1e-6}, ref)
    assert (gap, leaf) == (pytest.approx(1.0), "b")     # a leaf left unmoved
    assert check.worst_leaf_gap({"a": 10.0, "b": 0.0, "c": 1e-6}, ref,
                                frozenset({"b"}))[0] == 0.0
    with pytest.raises(KeyError):
        check.decide({"x": 1.0}, {})


# -- both drivers on the rehearsal configuration ------------------------------

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def counter():
    from benchmarks.harness.compiles import CompileCounter
    return CompileCounter()


def _line(workload: str, counter, seconds: float) -> tuple[dict, dict]:
    """Drive a run past the harness's look for a chip: the driver, the
    comparison and the result line, as run.py assembles them."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(REPO, "benchmarks", "run.py"))
    run_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_py)
    import importlib

    cell = registry.resolve(workload)
    driver = importlib.import_module(
        "benchmarks.drivers." + cell["config_file"]["driver"])
    out = driver.run(cell, SEED, seconds, False, time.perf_counter(),
                     counter)
    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    out["run"]["device"] = dev
    line = run_py.result_line(cell, out, dev, trace=False)
    assert list(json.loads(json.dumps(line))) == LINE_KEYS
    assert out["compiles_in_window"] == 0
    return line, out


TRAIN_FAULTS = ["none", "state_unchanged", "half_batch"]


@pytest.mark.parametrize("fault", TRAIN_FAULTS)
def test_train_driver_and_its_faults(fault, counter, monkeypatch):
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.train import trainer

    build = trainer.build_train_step

    def broken(*args, **kw):
        step = build(*args, **kw)
        if fault == "state_unchanged":
            def unchanged(state, batch):
                # The step runs, and hands back the parameters it was given.
                kept = jax.tree.map(jnp.copy, state.params)
                new, metrics = step(state, batch)
                return type(new)(step=new.step, params=kept,
                                 opt_state=new.opt_state), metrics
            return unchanged
        if fault == "half_batch":
            return lambda state, batch: step(
                state, {"tokens": batch["tokens"][:2]})
        return step

    monkeypatch.setattr(trainer, "build_train_step", broken)
    line, out = _line("rehearsal-tiny-train.stream-tiny", counter, 0.3)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["correct"] is (fault == "none"), line["checks"]
    over = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    if fault == "state_unchanged":
        assert line["checks"]["change_leaf_gap"]["value"] == pytest.approx(1)
    if fault == "half_batch":
        assert {"grad_norm_gap", "first_grad_leaf_gap"} <= over


def test_train_control_in_int8_comes_out_not_correct():
    from benchmarks.drivers import train
    from benchmarks.reference import decoder_f32 as ref

    cell = registry.resolve("rehearsal-tiny-train.stream-tiny")
    config = cell["config_file"]
    widths = ref.Widths.from_config(config)
    batches = traffic.train_batches(cell["traffic_file"], SEED,
                                    config["vocab_size"])
    trainer = config["trainer"]
    reference = ref.train_readings(SEED, widths, batches[:3],
                                   trainer["optimizer"],
                                   z_loss=trainer["z_loss"])
    planted = train.control_readings(SEED, widths, batches, trainer,
                                     reference)
    for name in ("int8", "half_batch"):
        correct, checks = check.decide(planted[name], config["limits"])
        assert not correct, (name, checks)


@pytest.mark.parametrize("fault", ["none", "token_altered"])
def test_serve_driver_and_an_altered_token(fault, counter, monkeypatch):
    from kubeflow_tpu.serving import continuous

    if fault == "token_altered":
        dispatch = continuous.ContinuousDecoder._dispatch

        def altered(self, toks, emitted):
            # Where a token is produced: every served token moves one up.
            return dispatch(self, (toks + 1) % self.cfg.vocab_size, emitted)

        monkeypatch.setattr(continuous.ContinuousDecoder, "_dispatch",
                            altered)
    line, out = _line("rehearsal-tiny-serve.chat-tiny", counter, 3.0)
    assert set(line["metrics"]) == {"itl_p95_ms", "serve_tokens_per_s",
                                    "setup_s"}
    assert registry.read_metrics(["ttft_p95_ms", "ttft_p50_ms"],
                                 out["run"]).keys() == {"ttft_p95_ms",
                                                        "ttft_p50_ms"}
    assert line["attempted"] == 18 and line["failed"] == 0
    assert line["correct"] is (fault == "none"), line["checks"]


def test_serve_control_in_int8_comes_out_not_correct():
    from benchmarks.drivers import serve
    from benchmarks.reference import decoder_f32 as ref

    config = registry.load_json("configs", "rehearsal-tiny-serve")
    widths = ref.Widths.from_config(config)
    rng = np.random.default_rng(SEED)
    sample = []
    for n in (40, 24, 12, 33):
        prompt = rng.integers(0, config["vocab_size"], n)
        # Greedy tokens of the reference itself: its own gap is nought.
        seq = list(prompt)
        for _ in range(12):
            logits = ref.logits_at(SEED, widths, np.array([seq]),
                                   np.array([[len(seq) - 1]]))
            seq.append(int(np.argmax(logits[0, 0])))
        sample.append((list(prompt), seq[n:]))
    assert serve.served_gaps(SEED, widths, sample)["widest_gap"] == 0.0
    planted = serve.served_gaps(SEED, widths, sample, lower="int8")
    assert planted["widest_gap"] > config["limits"]["served_logit_gap"]
