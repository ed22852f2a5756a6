"""CPU tests of ``benchmarks/harness/rounds.py``: hand-made spans and
module events through the ordered pairing and the reductions, and the
three readers over a made-up reduced trace, over a run that has nothing for
them, and over a program from before the round's record existed.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import registry, rounds, spans  # noqa: E402

ROUND_METRICS = ("admit_round_ms_p50", "late_token_share_pct",
                 "idle_in_admit_pct.serve")
CHAT_STEADY = "mistral-7b-v0.3-serve.chat-steady"

STEP = 5e-3   # a plain step
LAG = 1e-3    # the device plane's clock reads this early


def _one_ahead(n=8, first_launch=41, admit_at=None):
    """``n`` rounds of a decoder whose plain steps run one ahead, as the
    profiler would show them: the device runs back to back, the dispatch
    of launch L opens 0.3 ms into the round in which module L-1 runs, and
    the fetch of launch L-1 ends 0.1 ms after that module does. On the
    device's clock everything reads ``LAG`` early. With ``admit_at`` that
    round's module is an admission of 3 steps' length."""
    sched, modules = [], []
    t = 10.0                      # true start of the first module
    for i in range(n):
        launch = first_launch + i
        admit = i == admit_at
        took = 3 * STEP if admit else STEP
        kind = "admit" if admit else "decode"
        name = "jit_admit_rows_and_step(77)" if admit \
            else "jit_decode_step(123)"
        modules.append((name, t - LAG, t + took - LAG))
        # Enqueued while the module before it runs.
        opened = t - STEP + 0.3e-3
        sched.append(("sched.dispatch", opened, opened + 0.2e-3,
                      {"round": launch, "kind": kind, "launch": launch}))
        sched.append(("sched.fetch", t + 0.5e-3, t + took + 0.1e-3,
                      {"round": launch + 1, "kind": kind, "launch": launch}))
        t += took
    return sorted(sched, key=lambda s: s[1]), modules


def test_the_module_table_knows_every_step_function_of_the_program():
    from kubeflow_tpu.models import decode

    for fn, kinds in [(decode.admit_rows_and_step, ("admit", "chunk")),
                      (decode.admit_prefix_and_step, ("admit", "chunk")),
                      (decode.paged_admit_rows_and_step, ("admit", "chunk")),
                      (decode.paged_admit_prefix_and_step,
                       ("admit", "chunk")),
                      (decode.paged_prefill_chunk, ("chunk",)),
                      (decode.verify_chunk, ("verify",)),
                      (decode.extend_and_propose, ("draft",)),
                      (decode.decode_step, ("decode",)),
                      (decode.decode_chunk, ("decode",))]:
        assert rounds.module_kinds(f"jit_{fn.__name__}(4711)") == kinds
    for fn in (decode.copy_block, decode.store_prefix_row,
               decode.export_blocks, decode.import_blocks):
        assert rounds.module_kinds(f"jit_{fn.__name__}(1)") == ()


def test_ordered_pairing_reads_the_true_lag_where_nearest_is_a_step_off():
    sched, modules = _one_ahead()
    dispatched, fetched = rounds.launches(sched)
    assert sorted(dispatched) == list(range(41, 49))
    # Nearest pairing takes the module of the step BEFORE a dispatch for
    # its own (it is the nearest one before it): that one started 0.3 ms
    # before the dispatch opened, 1.3 ms on the device's clock, and the
    # lag reads a third over the true one whatever else the trace holds.
    starts = [s for name, s, _, _ in sched if name == "sched.dispatch"]
    nearest = spans.device_clock_lag(starts, [s for _, s, _ in modules])
    assert nearest == pytest.approx(LAG + 0.3e-3)
    paired = rounds.pair(dispatched, fetched, modules)
    assert paired["base"] == 41 and paired["pairs"] == 8
    # No module starts before its OWN dispatch even unmoved (each waits
    # for the one before it), so the least shift is none; the most that
    # still ends every module before its fetch is the true lag + 0.1 ms.
    assert paired["lag_s"] == 0.0
    assert paired["lag_max_s"] == pytest.approx(LAG + 0.1e-3)
    # An idle device: the first module starts 50 us after its dispatch
    # opened, and the least shift is the true lag to those 50 us, where
    # the nearest pairing still reads 1.3 ms.
    first = sched[0]
    sched[0] = (first[0], modules[0][1] + LAG - 50e-6,
                modules[0][1] + LAG, first[3])
    paired = rounds.pair(*rounds.launches(sched), modules)
    assert paired["lag_s"] == pytest.approx(LAG - 50e-6)
    starts[0] = sched[0][1]
    assert spans.device_clock_lag(starts, [s for _, s, _ in modules]) \
        == pytest.approx(LAG + 0.3e-3)


def test_pairing_is_anchored_by_kind_and_else_by_the_fetch():
    # The device plane began two modules before the host plane did and
    # ends one short: the kinds put the one admission under its dispatch.
    sched, modules = _one_ahead(n=10, admit_at=6)
    kept = [s for s in sched if s[3]["launch"] >= 43]
    paired = rounds.pair(*rounds.launches(kept), modules[:-1])
    assert paired["base"] == 41 and paired["pairs"] == 7
    # Plain steps alone: every anchoring agrees in kind, and the modules
    # that end just before their fetch does are the right ones.
    sched, modules = _one_ahead(n=12)
    kept = [s for s in sched if s[3]["launch"] >= 44]
    paired = rounds.pair(*rounds.launches(kept), modules)
    assert paired["base"] == 41 and paired["pairs"] == 9
    assert paired["lag_max_s"] == pytest.approx(LAG + 0.1e-3)
    # A draft model's module has no dispatch span and keeps its place.
    sched, modules = _one_ahead(n=6)
    sched = [s for s in sched if s[3]["launch"] != 43]
    modules[2] = ("jit_extend_and_propose(9)",) + modules[2][1:]
    assert rounds.pair(*rounds.launches(sched), modules)["pairs"] == 5
    # A module that is no launch is left out before pairing.
    assert rounds.module_kinds("jit_copy_block(5)") == ()
    assert rounds.pair({}, {}, modules) is None
    assert rounds.pair(*rounds.launches(sched), []) is None


def test_a_kind_that_disagrees_raises_and_names_the_pair():
    sched, modules = _one_ahead(n=8, admit_at=3)
    # The program says launch 44 was a plain step; the trace holds an
    # admission module in its place, and no other anchoring fits either.
    sched = [(name, s, e, {**args, "kind": "decode"})
             for name, s, e, args in sched]
    with pytest.raises(RuntimeError) as err:
        rounds.pair(*rounds.launches(sched), modules, reach=2)
    assert "launch 44 is a 'decode' dispatch" in str(err.value)
    assert "'jit_admit_rows_and_step'" in str(err.value)


def _round(start, wall, **args):
    return ("sched.round", start, start + wall, {"round": 1, **args})


ROUNDS = [
    _round(0.0, 0.0045, kind="decode", active=4, admitted=0, routed=4,
           routed_late=0, host_wall_us=700, host_cpu_us=650),
    _round(0.0045, 0.0135, kind="admit", active=4, admitted=1, routed=9,
           routed_late=4, host_wall_us=1500, host_cpu_us=900),
    # The first admission into an empty batch stretches nobody's gap.
    _round(0.018, 0.0100, kind="admit", active=0, admitted=2, routed=2,
           routed_late=0, host_wall_us=1000, host_cpu_us=1010),
    _round(0.028, 0.0200, kind="chunk", active=3, admitted=0, routed=3,
           routed_late=0, host_wall_us=800, host_cpu_us=800),
    _round(0.048, 0.0300, kind="decode", active=3, admitted=1, routed=2,
           routed_late=2, host_wall_us=600, host_cpu_us=450),
    # Still open as the trace ended: no closing arguments.
    ("sched.round", 0.078, 0.080, {"round": 6, "active": 3}),
]


def test_the_reductions_on_a_made_up_span_list():
    closed = rounds.round_args(ROUNDS)
    assert len(closed) == 5
    # 4,600 us of host wall and 3,810 of thread CPU over five rounds.
    assert rounds.host_ms_per_round(closed) == pytest.approx((0.92, 0.762))
    # 13.5, 20 and 30 ms: the rounds that prefilled beside live rows.
    assert rounds.admit_round_ms_p50(closed) == pytest.approx(20.0)
    assert rounds.late_token_share_pct(closed) == pytest.approx(30.0)
    # Idle 2 ms early: moved by the lag it straddles the admit round's
    # start (1 ms inside); one wholly inside the chunk round; one in the
    # empty batch's admission; one inside a decode round; a pause of 10 us.
    gaps = [(0.0015, 0.0035), (0.029, 0.030), (0.0185, 0.0195),
            (0.055, 0.060), (0.009, 0.00901)]
    assert rounds.idle_in_admission_s(gaps, closed, 2e-3) == \
        pytest.approx(1e-3 + 1e-3 + 1e-3)
    old = [(s, e, {"kind": "decode", "active": 1, "admitted": 0})
           for s, e, _ in closed]
    assert rounds.host_ms_per_round(old) is None
    assert rounds.late_token_share_pct(old) is None
    assert rounds.admit_round_ms_p50(old) is None


@pytest.mark.parametrize("name", ROUND_METRICS)
def test_a_round_reader_finds_nothing_where_there_is_nothing(name):
    read = registry.metric_reader(name)
    for kind in ("serve", "train"):
        assert read({"kind": kind, "trace": None}) is None
    assert read({"kind": "train",
                 "trace": {"busy_s": 1.0, "window_s": 2.0}}) is None


@pytest.mark.parametrize("name", ROUND_METRICS)
def test_a_round_reader_reads_a_reduced_trace(name, monkeypatch):
    sched, modules = _one_ahead(n=8, admit_at=3)
    # The admission's round, as the record closes it: launch 44's module
    # runs 10.015 .. 10.030; the device pauses 0.6 ms before it (on its own
    # clock, 1 ms early), which the unmoved times put before the round.
    admit = _round(10.0146, 0.016, kind="admit", active=4, admitted=1,
                   routed=9, routed_late=4, host_wall_us=1500,
                   host_cpu_us=900)
    plain = _round(10.0306, 0.0045, kind="decode", active=5, admitted=0,
                   routed=5, routed_late=0, host_wall_us=700,
                   host_cpu_us=700)
    reduced = {"scoped": True, "sched": sched + [admit, plain],
               "device_clock_lag_s": 5.7e-3}
    timeline = {"modules": modules, "others": {},
                "gaps": [(10.0138, 10.0144)]}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "some.xplane.pb")
    monkeypatch.setattr(spans, "load", lambda path: reduced)
    monkeypatch.setattr(rounds, "device_timeline", lambda path: timeline)
    run = {"kind": "serve", "trace": {"busy_s": 3.9, "window_s": 4.0}}
    want = {"admit_round_ms_p50": 16.0,
            "late_token_share_pct": 100.0 * 4 / 14,
            # The admission's dispatch opens 0.6 ms after its module
            # starts on the device's clock: moved by that lag, the pause
            # before the module ends 0.4 ms inside the admission's round.
            "idle_in_admit_pct.serve": 100.0 * 0.4e-3 / 4.0}
    if name == "idle_in_admit_pct.serve":
        # Make the admission's dispatch open on an idle device.
        i = next(k for k, s in enumerate(sched)
                 if s[0] == "sched.dispatch" and s[3]["launch"] == 44)
        sched[i] = ("sched.dispatch", 10.0146, 10.0148, sched[i][3])
        reduced["sched"] = sorted(sched + [admit, plain], key=lambda s: s[1])
        paired = rounds.pair(*rounds.launches(reduced["sched"]), modules)
        assert paired["lag_s"] == pytest.approx(0.6e-3)
    assert registry.metric_reader(name)(run) == pytest.approx(want[name])

    # A program from before the round's record: spans without ``launch``
    # and rounds that close with ``kind`` and ``admitted`` alone. Nothing
    # raises; the one reading that needs no new argument is still given.
    before = [(n, s, e, {k: v for k, v in args.items()
                         if k in ("round", "kind", "active", "admitted")})
              for n, s, e, args in reduced["sched"]]
    monkeypatch.setattr(spans, "load",
                        lambda path: {**reduced, "sched": before})
    monkeypatch.setattr(rounds, "device_timeline", lambda path: 1 / 0)
    got = registry.metric_reader(name)(run)
    assert got == (pytest.approx(16.0) if name == "admit_round_ms_p50"
                   else None)
    # And one that set no sched.* span at all.
    monkeypatch.setattr(spans, "load", lambda path: {
        "scoped": False, "sched": [], "device_clock_lag_s": 0.0})
    assert registry.metric_reader(name)(run) is None


def test_the_round_metrics_are_listed_last_with_their_cells():
    bench = registry.benchmark_json()
    listed = bench["per_layer"][-len(ROUND_METRICS):]
    assert [m["name"] for m in listed] == list(ROUND_METRICS)
    for entry in listed:
        assert entry["layer"] == "scheduler"
        assert entry["source"] == "program_span"
        assert entry["better"] == "lower"
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "metrics", entry["name"] + ".py"))
    # ISSUE 38's fourth, sched_off_cpu_ms_per_round, is not listed: what
    # it would read cannot be had from one read of the thread's CPU clock
    # a round (rounds.host_ms_per_round says why).
    assert all(m["workloads"] == [CHAT_STEADY] for m in listed)
    assert [m["moves"] for m in listed] == [
        "itl_p95_ms", "itl_p95_ms", "serve_tokens_per_s"]


def test_the_quoted_span_names_are_the_programs():
    from kubeflow_tpu.observability import tracing

    assert rounds.DISPATCH == tracing.SPAN_PREFIX + "dispatch"
    assert rounds.FETCH == tracing.SPAN_PREFIX + "fetch"
    record = tracing.RoundRecord(1, 0, 0.0, 0.0, 0.0)
    record.close("decode", 0, 1.0, 0.5)
    assert {"kind", "admitted", "routed", "routed_late", "host_wall_us",
            "host_cpu_us"} <= set(record.span_metadata())
