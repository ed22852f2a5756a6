"""CPU tests of ``benchmarks/harness/spans.py``: hand-made event lists and
a hand-made instruction graph through the reductions, the wire-format
reader against a module JAX compiles here, and the seven readers over a
run that has nothing for them (a CPU run, another cell's run, a program
from before the names existed).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import registry, spans  # noqa: E402

NEW_METRICS = ("weight_cast_share_pct", "prefill_scope_share_pct",
               "idle_in_fetch_pct.serve", "idle_in_host_pct.serve",
               "sched_host_ms_per_round", "train_head_loss_share_pct",
               "train_mlp_share_pct")


def test_the_quoted_names_are_the_programs():
    from kubeflow_tpu.observability import tracing

    assert spans.DEVICE_SCOPES == tracing.DEVICE_SCOPES
    assert spans.SCHED_PHASES == tracing.SCHED_PHASES
    assert (spans.SPAN_PREFIX, spans.SPAN_ROUND) == (tracing.SPAN_PREFIX,
                                                     tracing.SPAN_ROUND)
    assert spans.CAST == tracing.SCOPE_CAST_WEIGHTS
    assert set(spans.HOST_PHASES) == set(spans.SCHED_PHASES) - {"idle",
                                                                 "fetch"}


@pytest.mark.parametrize("op_name, path", [
    ("jit(decode_step)/decode/while/body/closed_call/attn/cast_weights/"
     "convert_element_type", ("decode", "attn", "cast_weights")),
    ("jit(sharded_step)/transpose(jvp(head_loss))/jit(take_along_axis)/"
     "gather", ("head_loss",)),
    ("jit(sharded_step)/jvp()/while/body/closed_call/mlp/jit(silu)/neg",
     ("mlp",)),
    ("jit(f)/transpose(jvp())/attn/reshape;attn/mul", ("attn",)),
    ("jit(sharded_step)/optimizer/jit(clip)/max", ("optimizer",)),
    # The last component is the primitive, whatever it is called.
    ("jit(f)/decode", ()),
    ("jit(step)/convert_element_type", ()),
    ("", ()),
])
def test_scope_path_reads_the_programs_scopes_outermost_first(op_name, path):
    assert spans.scope_path(op_name) == path


def test_nested_events_book_to_the_innermost_and_sum_to_the_union():
    # A while op encloses its body's ops; a fused op stands alone.
    events = [
        (("decode",), 0.0, 10.0),                      # the while itself
        (("decode", "attn"), 1.0, 3.0),
        (("decode", "attn", "cast_weights"), 3.0, 4.0),
        (("decode", "mlp"), 5.0, 9.0),
        ((), 10.0, 11.0),                              # nothing names it
        (("sample",), 12.0, 12.5),
    ]
    got = spans.by_scope(spans.self_times(events))
    assert got == pytest.approx({
        "decode": 3.0, "decode/attn": 2.0, "decode/attn/cast_weights": 1.0,
        "decode/mlp": 4.0, "unscoped": 1.0, "sample": 0.5})
    # Parts sum to the whole: the union of the intervals.
    assert sum(got.values()) == pytest.approx(11.5)
    assert spans.under(got, "decode") == pytest.approx(10.0)
    assert spans.under(got, "cast_weights") == pytest.approx(1.0)
    assert spans.under(got, "prefill") == 0.0
    assert spans.innermost(got) == pytest.approx({
        "decode": 3.0, "attn": 2.0, "cast_weights": 1.0, "mlp": 4.0,
        "unscoped": 1.0, "sample": 0.5})


def test_a_gap_is_split_among_the_spans_that_cover_it():
    spans_ = [("dispatch", 0.0, 1.0), ("fetch", 1.0, 5.0),
              ("route", 5.0, 6.0), ("plan", 6.5, 7.0)]
    gaps = [(4.0, 6.75),      # fetch 1.0, route 1.0, nothing 0.5, plan 0.25
            (8.0, 9.0)]       # under no span
    got = spans.split_idle(gaps, spans_)
    assert got == pytest.approx({"fetch": 1.0, "route": 1.0, "plan": 0.25,
                                 "uncovered": 1.5})
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in gaps))
    assert spans.split_idle(gaps, []) == {"uncovered": pytest.approx(3.75)}


def test_device_times_move_by_the_least_that_restores_causality():
    modules = [0.9995, 1.0248, 1.0502, 2.0]
    # The second dispatch starts 0.3 ms after "its" module did: the device
    # clock reads at least that early. The first is 0.1 ms before its own.
    assert spans.device_clock_lag([0.9996, 1.0251, 1.0503], modules) == \
        pytest.approx(3e-4)
    assert spans.device_clock_lag([0.9990, 1.0240], modules) == 0.0
    assert spans.device_clock_lag([], modules) == 0.0
    assert spans.device_clock_lag([1.5], []) == 0.0
    # A module further back than the reach belongs to another round.
    assert spans.device_clock_lag([1.5], [1.4]) == 0.0


def _ins(name, id_, opcode, operands=(), op_name="", comp=1, **kw):
    return {"name": name, "id": id_, "opcode": opcode, "op_name": op_name,
            "operands": list(operands), "called": [], "comp": comp,
            "tuple_index": 0, "parameter_number": 0, "root": False,
            "entry": comp == 1, **kw}


def test_an_unnamed_instruction_is_booked_to_what_consumes_it():
    body, fused = 2, 3
    graph = [
        # Entry: two weights cast as whole stacks outside the layer loop,
        # as XLA:TPU leaves them (no op_name), and carried into it.
        _ins("w_up", 1, "parameter", op_name="params['layers']['mlp']['up']"),
        _ins("w_q", 2, "parameter", parameter_number=1,
             op_name="params['layers']['attn']['wq']"),
        _ins("cache", 3, "parameter", parameter_number=2,
             op_name="state['cache']['k']"),
        _ins("convert.1", 4, "convert", [1]),
        _ins("convert.2", 5, "convert", [2]),
        _ins("convert.3", 6, "convert", [3]),
        _ins("tuple.1", 7, "tuple", [4, 5, 6]),
        _ins("while.1", 8, "while", [7], op_name="jit(f)/decode/while",
             called=[body]),
        _ins("copy.9", 9, "copy", [8]),            # named by what it reads
        # The loop body: up is sliced inside a fusion whose slice still
        # says cast_weights; wq's label is gone, its matmul is in attn.
        _ins("arg", 20, "parameter", comp=body),
        _ins("gte.0", 21, "get-tuple-element", [20], comp=body),
        _ins("gte.1", 22, "get-tuple-element", [20], comp=body,
             tuple_index=1),
        _ins("gte.2", 23, "get-tuple-element", [20], comp=body,
             tuple_index=2),
        _ins("fusion.1", 24, "fusion", [21], comp=body, called=[fused],
             op_name="jit(f)/decode/while/body/closed_call/mlp/dot_general"),
        _ins("slice.1", 25, "dynamic-slice", [22], comp=body,
             op_name="jit(f)/decode/while/body/dynamic_slice"),
        _ins("dot.1", 26, "dot", [25], comp=body,
             op_name="jit(f)/decode/while/body/closed_call/attn/dot_general"),
        _ins("update.1", 27, "dynamic-update-slice", [23], comp=body,
             op_name="jit(f)/decode/while/body/closed_call/attn/scatter"),
        _ins("p0", 30, "parameter", comp=fused),
        _ins("slice.2", 31, "dynamic-slice", [30], comp=fused,
             op_name="jit(f)/decode/while/body/closed_call/mlp/cast_weights/"
                     "convert_element_type"),
        # A fusion with no op_name of its own takes its root's.
        _ins("fusion.2", 40, "fusion", [9], called=[4]),
        _ins("root", 41, "add", comp=4, root=True,
             op_name="jit(f)/sample/add"),
    ]
    paths = spans.resolve_paths(graph)
    assert paths["convert.1"] == ("decode", "mlp", "cast_weights")
    # No consumer says it any more, but it casts an argument named params.
    assert paths["convert.2"] == ("decode", "attn", "cast_weights")
    # A convert of anything else is not a weight cast.
    assert paths["convert.3"] == ("decode", "attn")
    assert paths["copy.9"] == ("sample",)
    assert paths["fusion.2"] == ("sample",)
    assert paths["dot.1"] == ("decode", "attn")
    assert paths["w_up"] == ()


def test_the_wire_reader_reads_a_module_compiled_here():
    """Names, opcodes and op_names of a compiled module's serialized proto
    are the ones its text shows, so the reader's field numbers hold."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.observability.tracing import scope

    @jax.jit
    def f(w, x):
        with scope("mlp"):
            with scope("cast_weights"):
                w = w.astype(jnp.bfloat16)
            return jnp.tanh(x @ w)

    compiled = f.lower(jnp.ones((8, 8)), jnp.ones((4, 8), jnp.bfloat16)
                       ).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    proto = module.as_serialized_hlo_module_proto()
    assert len(proto) < 1 << 14   # a two-byte length is enough here
    wrapped = b"\x0a" + bytes([len(proto) & 0x7F | 0x80, len(proto) >> 7]) \
        + proto
    instructions = spans.hlo_instructions(memoryview(wrapped))
    text = module.to_string()
    named = dict(re.findall(
        r"%?([\w.\-]+) = [^\n]*?op_name=\"([^\"]+)\"", text))
    assert named, text
    by_name = {i["name"]: i for i in instructions}
    for name, op_name in named.items():
        assert by_name[name]["op_name"] == op_name
    assert {i["opcode"] for i in instructions} >= {"parameter"}
    assert sum(i["root"] for i in instructions) == len(
        {i["comp"] for i in instructions})
    assert any(i["entry"] and i["opcode"] == "parameter"
               for i in instructions)
    paths = spans.resolve_paths(instructions)
    assert ("mlp", "cast_weights") in paths.values() or \
        ("mlp",) in paths.values()


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_there_is_nothing_to_read(name):
    read = registry.metric_reader(name)
    trace = {"busy_s": 1.0, "window_s": 2.0}
    # A run with no trace, and a traced run of the other kind of cell.
    for kind in ("serve", "train"):
        assert read({"kind": kind, "trace": None}) is None
    other = "train" if "train" not in name else "serve"
    assert read({"kind": other, "trace": trace}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_reads_a_reduced_trace(name, monkeypatch):
    reduced = {
        "scoped": True,
        "scope_s": {"prefill/attn": 0.2, "decode/mlp/cast_weights": 0.3,
                    "decode/mlp": 0.1, "mlp": 0.5, "head_loss": 0.25,
                    "unscoped": 0.05},
        "sched": [("sched.round", 0.0, 1.0, {"round": 1})], "rounds": 4,
        "phase_s": {"idle": 0.0, "plan": 0.001, "build": 0.002,
                    "dispatch": 0.003, "fetch": 0.5, "route": 0.002},
        "idle_in_s": {"fetch": 0.1, "plan": 0.01, "dispatch": 0.02,
                      "route": 0.01, "uncovered": 0.0},
    }
    monkeypatch.setattr(spans, "newest_xplane", lambda: "some.xplane.pb")
    monkeypatch.setattr(spans, "load", lambda path: reduced)
    kind = "train" if "train" in name else "serve"
    run = {"kind": kind, "trace": {"busy_s": 1.0, "window_s": 2.0}}
    want = {"weight_cast_share_pct": 30.0, "prefill_scope_share_pct": 20.0,
            "idle_in_fetch_pct.serve": 5.0, "idle_in_host_pct.serve": 2.0,
            "sched_host_ms_per_round": 2.0,
            "train_head_loss_share_pct": 25.0, "train_mlp_share_pct": 90.0}
    assert registry.metric_reader(name)(run) == pytest.approx(want[name])

    # A program from before the names existed: nothing to read, no error,
    # though its unnamed weight converts are still told for what they are.
    bare = {**reduced, "scoped": False, "sched": [],
            "scope_s": {"unscoped": 0.6, "cast_weights": 0.4}}
    monkeypatch.setattr(spans, "load", lambda path: bare)
    assert registry.metric_reader(name)(run) is None

    # The program's names are there but this reader's is not: it raises,
    # naming what the trace holds (the cast share reads 0.0 instead).
    gone = {**reduced, "scope_s": {"decode/attn": 1.0},
            "phase_s": {p: 0.0 for p in spans.SCHED_PHASES}, "rounds": 0}
    monkeypatch.setattr(spans, "load", lambda path: gone)
    if name == "weight_cast_share_pct":
        assert registry.metric_reader(name)(run) == 0.0
    else:
        with pytest.raises(RuntimeError, match="holds"):
            registry.metric_reader(name)(run)


def test_every_new_metric_is_listed_with_its_cell_and_has_a_reader():
    bench = registry.benchmark_json()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        entry = listed[name]
        assert len(entry["workloads"]) == 1
        assert ("train" in entry["workloads"][0]) == ("train" in name)
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "metrics", name + ".py"))
