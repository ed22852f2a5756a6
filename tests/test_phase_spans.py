"""The program's phase names on the profiler's clock
(observability/tracing.py's tables): every device scope is in the compiled
HLO of the executables that set it, every ``sched.*`` span is in the host
plane of a CPU profile of a live decoder with its ``round`` argument, the
request timelines join on that round, and the always-on phase counter
renders lint-clean. A refactor that drops a name fails here, not on the
chip."""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import spans
from kubeflow_tpu.models import decode
from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.models.transformer import serving_params
from kubeflow_tpu.observability import tracing
from kubeflow_tpu.observability.lint import lint
from kubeflow_tpu.serving.continuous import ContinuousDecoder


@pytest.fixture(scope="module")
def model():
    spec = get_model("lm-test-tiny")
    return spec, spec.init(jax.random.PRNGKey(0), spec.config)


def _scopes(compiled) -> set:
    """Every program scope in the op_name metadata of a compiled module."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]+)"', compiled.as_text()):
        found.update(spans.scope_path(op_name))
    return found


MODEL_SCOPES = {tracing.SCOPE_EMBED, tracing.SCOPE_ATTN, tracing.SCOPE_MLP,
                tracing.SCOPE_CAST_WEIGHTS}


def test_decode_step_carries_its_scopes(model):
    spec, params = model
    state = decode.init_decode_state(spec.config, 2, 24)
    compiled = decode.decode_step.lower(state, params, spec.config).compile()
    assert _scopes(compiled) == MODEL_SCOPES | {
        tracing.SCOPE_DECODE, tracing.SCOPE_SAMPLE, tracing.SCOPE_HEAD}


def test_admission_carries_prefill_and_its_fused_step_decode(model):
    spec, params = model
    state = decode.init_decode_state(spec.config, 2, 24)
    compiled = decode.admit_rows_and_step.lower(
        state, params, spec.config, jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32),
        jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.float32)).compile()
    assert _scopes(compiled) == MODEL_SCOPES | {
        tracing.SCOPE_PREFILL, tracing.SCOPE_DECODE, tracing.SCOPE_SAMPLE,
        tracing.SCOPE_HEAD}
    # The fused step inside an admission module is decode, not prefill.
    paths = {spans.scope_path(n) for n in
             re.findall(r'op_name="([^"]+)"', compiled.as_text())}
    assert (tracing.SCOPE_DECODE, tracing.SCOPE_ATTN) in paths
    assert (tracing.SCOPE_PREFILL, tracing.SCOPE_ATTN) in paths
    assert not any(tracing.SCOPE_PREFILL in p and tracing.SCOPE_DECODE in p
                   for p in paths)


def test_a_serving_tree_leaves_no_cast_in_the_decode_step(model):
    """``weight_cast_share_pct`` reads 0.0 for this reason: on the tree a
    replica holds, cast_param emits nothing, so no instruction of the step
    carries the scope. It stays in the train step (below) and in a step
    lowered on float32 (above)."""
    spec, params = model
    state = decode.init_decode_state(spec.config, 2, 24)
    compiled = decode.decode_step.lower(
        state, serving_params(params, spec.config), spec.config).compile()
    assert tracing.SCOPE_CAST_WEIGHTS not in compiled.as_text()
    assert _scopes(compiled) == MODEL_SCOPES - {
        tracing.SCOPE_CAST_WEIGHTS} | {
        tracing.SCOPE_DECODE, tracing.SCOPE_SAMPLE, tracing.SCOPE_HEAD}


def test_train_step_carries_its_scopes(model):
    from kubeflow_tpu.parallel.mesh import single_device_mesh
    from kubeflow_tpu.train.optimizers import OptimizerConfig
    from kubeflow_tpu.train.trainer import build_train_step, init_state

    spec, _ = model
    mesh = single_device_mesh(jax.devices()[0])
    opt = OptimizerConfig(name="adafactor")
    state = init_state(jax.random.PRNGKey(0), spec, opt, mesh)
    compiled = build_train_step(spec, opt, mesh).lower(
        state, {"tokens": jnp.zeros((2, 17), jnp.int32)}).compile()
    # Training keeps float32 masters and casts inside the step.
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree.leaves(state.params))
    assert _scopes(compiled) == MODEL_SCOPES | {
        tracing.SCOPE_HEAD_LOSS, tracing.SCOPE_OPTIMIZER}
    # Backward ops stay in the forward's scope.
    assert re.search(r'op_name="[^"]*transpose\(jvp\(head_loss\)\)',
                     compiled.as_text())


def test_every_device_scope_is_set_somewhere():
    """The three tests above cover the whole table between them."""
    covered = MODEL_SCOPES | {
        tracing.SCOPE_DECODE, tracing.SCOPE_SAMPLE, tracing.SCOPE_HEAD,
        tracing.SCOPE_PREFILL, tracing.SCOPE_HEAD_LOSS,
        tracing.SCOPE_OPTIMIZER}
    assert covered == set(tracing.DEVICE_SCOPES)


def test_scopes_are_part_of_the_compile_cache_key():
    """Else a cached executable from a build with other names is loaded
    with them (JAX's default leaves op metadata out of the key)."""
    assert decode.scope is tracing.scope
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def _profile(tmp_path, work):
    """Run ``work()`` under a profiler session; the scheduler's spans."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    return spans.sched_spans(spans.read_planes(path))


def test_scheduler_spans_and_timelines_join_on_the_round(model, tmp_path):
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8)
    try:
        d.generate([1, 2, 3], 2, timeout=60)   # compile outside the trace

        def work():
            # The round that was waiting as the trace began has no span
            # of its own (it began untraced): let it pass.
            d.generate([9], 1, timeout=60)
            d.submit([1, 2, 3], 4, request_id="first").result(timeout=60)
            time.sleep(0.05)                   # the loop idles, then wakes
            handles = [d.submit([4 + i, 5], 3, request_id=f"burst-{i}")
                       for i in range(3)]
            for h in handles:
                h.result(timeout=60)

        sched = _profile(tmp_path, work)
        names = {name for name, *_ in sched}
        assert names == {tracing.SPAN_ROUND} | {
            tracing.SPAN_PREFIX + p for p in tracing.SCHED_PHASES}
        assert all(isinstance(args.get("round"), int) and args.get("kind")
                   for name, _, _, args in sched
                   if name != tracing.SPAN_ROUND)
        rounds = {args["round"]: args for name, _, _, args in sched
                  if name == tracing.SPAN_ROUND}
        assert all({"active", "admitted", "kind"} <= set(a)
                   for a in rounds.values())
        # A phase lies inside the round whose number it carries.
        span_of = {args["round"]: (s, e) for name, s, e, args in sched
                   if name == tracing.SPAN_ROUND}
        for name, s, e, args in sched:
            if name != tracing.SPAN_ROUND and args["round"] in span_of:
                r0, r1 = span_of[args["round"]]
                assert r0 <= s and e <= r1 + 1e-6, (name, args)
        for rid in ("first", "burst-0", "burst-1", "burst-2"):
            tl, = d.trace.find(rid)
            at = {e["name"]: e for e in tl["events"]}
            admitted, first = at["admitted"]["round"], \
                at["first_token"]["round"]
            assert rounds[admitted]["admitted"] >= 1
            # One dispatch prompt→token: the admitting round's own step.
            assert first == admitted
            assert tl["dropped_events"] == 0
            assert at["finish"]["tokens"] == (4 if rid == "first" else 3)
            assert at["finish"]["rounds"] >= 1
            assert "dispatch" not in at
    finally:
        d.stop()


def test_round_kinds_show_the_ramp_streak_cap(model, tmp_path):
    """Under sustained arrivals the TTFT ramp must not degrade chunked
    dispatch toward one dispatch per token: while streams are decoding, an
    admission-only round (kind ``admit``) is never followed by another —
    the second admitting round also runs its chunk (kind ``decode``)."""
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=16, prefill_len=16,
                          max_new_tokens=32, chunk_size=4)
    try:
        d.generate([1, 2, 3], 6, timeout=60)   # compile outside the trace

        def work():
            d.generate([9], 1, timeout=60)     # past the untraced round
            long_req = d.submit([1, 2, 3], 32)
            next(long_req.tokens(timeout=60))  # admitted, past its ramp
            shorts = []
            for i in range(12):                # one arrival a round, or so
                seen = d._round
                while d._round == seen:
                    time.sleep(0.0002)
                shorts.append(d.submit([5 + i], 3))
            for h in shorts:
                assert len(h.result(timeout=60)["tokens"]) == 3
            assert len(long_req.result(timeout=60)["tokens"]) == 32

        pairs = []
        for attempt in range(3):               # until arrivals straddle rounds
            sched = _profile(tmp_path / str(attempt), work)
            rounds = sorted(
                (args["round"], args) for name, _, _, args in sched
                if name == tracing.SPAN_ROUND)
            pairs = [(a0["kind"], a1["kind"])
                     for (n0, a0), (n1, a1) in zip(rounds, rounds[1:])
                     if n1 == n0 + 1 and a0["admitted"] and a1["admitted"]
                     and a0["active"] and a1["active"]]
            assert ("admit", "admit") not in pairs, rounds
            if pairs:
                break
        assert pairs, "no two consecutive admitting rounds were seen"
    finally:
        d.stop()


def _by_round(sched):
    """``{round: {span name: [arguments, in start order]}}``."""
    out = {}
    for name, _, _, args in sched:
        out.setdefault(args["round"], {}).setdefault(name, []).append(args)
    return out


DISPATCH = tracing.SPAN_PREFIX + "dispatch"
FETCH = tracing.SPAN_PREFIX + "fetch"


def test_every_dispatch_carries_its_launch_and_none_is_missing(model,
                                                               tmp_path):
    """Admit, chunk and decode rounds of a decoder built inside the
    capture: the ``launch`` ordinals of its ``sched.dispatch`` spans run 1,
    2, 3 … in start order, a ``sched.fetch`` names the launch whose result
    it waits for, and a plain round's is the launch before its own
    dispatch's (plain steps run one ahead)."""
    spec, params = model
    made = []

    def work():
        d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                              max_new_tokens=16, kv_layout="paged",
                              kv_block_size=8, prefill_chunk_tokens=8,
                              max_prompt_len=40)
        made.append(d)
        short = d.submit([1, 2, 3], 16)
        next(short.tokens(timeout=120))          # live beside the chunks
        long_req = d.submit(list(range(1, 29)), 3)   # 28 tokens: 4 chunks
        assert len(long_req.result(timeout=120)["tokens"]) == 3
        assert len(short.result(timeout=120)["tokens"]) == 16

    try:
        sched = _profile(tmp_path, work)
        d, = made
        dispatches = [args for name, _, _, args in sched if name == DISPATCH]
        assert [a["launch"] for a in dispatches] == list(
            range(1, len(dispatches) + 1))
        assert len(dispatches) == d._launches
        assert {a["kind"] for a in dispatches} == {"admit", "chunk", "decode"}
        launched = {a["launch"] for a in dispatches}
        fetches = [args for name, _, _, args in sched if name == FETCH]
        assert fetches and all(a["launch"] in launched for a in fetches)
        plain = [spans_ for spans_ in _by_round(sched).values()
                 if [a["kind"] for a in spans_.get(DISPATCH, [])] == ["decode"]
                 and len(spans_.get(FETCH, [])) == 1
                 and tracing.SPAN_PREFIX + "build" not in spans_]
        assert plain
        for spans_ in plain:
            assert spans_[FETCH][0]["launch"] \
                == spans_[DISPATCH][0]["launch"] - 1
        # The span's closing arguments are the record's (the capture may
        # end before the last round's span does).
        closed = {n: spans_[tracing.SPAN_ROUND][0]
                  for n, spans_ in _by_round(sched).items()
                  if tracing.SPAN_ROUND in spans_}
        assert len(closed) >= len(d.rounds.recent()) - 1
        for rec in d.rounds.recent():
            if rec.round in closed:
                args = closed[rec.round]
                assert {k: args[k] for k in rec.span_metadata()} \
                    == rec.span_metadata()
        chunk_rounds = [r for r in d.rounds.recent() if r.kind == "chunk"
                        or (r.kind == "decode" and r.prompt_tokens)]
        assert sum(r.prompt_tokens for r in d.rounds.recent()) \
            == d.metrics()["prefill_tokens"] == 3 + 28
        assert chunk_rounds
    finally:
        for d in made:
            d.stop()


def _settled(d):
    """The decoder's records once the round that served the last token has
    closed (a result is handed back from inside its ``route``)."""
    deadline = time.time() + 10
    while time.time() < deadline and sum(
            r.routed for r in d.rounds.recent()) \
            < d.metrics()["tokens_emitted"]:
        time.sleep(0.001)
    return d.rounds.recent()


def test_round_records_count_what_was_routed_and_what_came_late(model):
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=48)
    try:
        d.generate([1, 2, 3], 20, timeout=60)        # plain rounds only
        plain = _settled(d)
        assert sum(r.routed for r in plain) == d.metrics()["tokens_emitted"]
        assert all(r.routed_late == 0 for r in plain)
        assert [r.round for r in plain] == list(range(1, len(plain) + 1))
        first = d.submit([1, 2, 3], 48)
        next(first.tokens(timeout=60))
        second = d.submit([4, 5], 4)                 # admitted beside it
        second.result(timeout=60)
        first.result(timeout=60)
        recs = _settled(d)
        assert sum(r.routed for r in recs) == d.metrics()["tokens_emitted"]
        beside, = [r for r in recs if r.admitted and r.active]
        # The live row's token waited for the prefill; the new row's first
        # token has no gap to stretch.
        assert beside.routed_late == 1 and beside.kind == "admit"
        assert beside.routed >= 2 and beside.prompt_tokens == 2
        assert sum(r.routed_late for r in recs) == 1
        for r in recs:
            # One thread's CPU seconds never exceed the round's own (two
            # clocks, read a fraction of a microsecond apart).
            assert 0 <= r.host_cpu_s <= r.wall_s + 1e-5
            assert 0 <= r.host_wall_s <= r.wall_s
            assert r.launches in (0, 1) and r.other_s >= 0
            assert abs(sum(r.phase_s.values()) + r.other_s - r.wall_s) < 1e-9
        launches = [r.first_launch for r in recs if r.launches]
        assert launches == list(range(1, len(launches) + 1))
        # Rounds tile the thread's time: one starts where the last ended.
        for a, b in zip(recs, recs[1:]):
            assert abs(a.t_wall + a.wall_s - b.t_wall) < 5e-3
    finally:
        d.stop()


def test_the_ring_holds_the_newest_rounds_and_drops_the_oldest():
    rounds = tracing.RoundLog()
    for n in range(1, rounds.CAPACITY + 101):
        rec = tracing.RoundRecord(n, 0, 0.0, 0.0, 0.0)
        rec.close("decode", 0, 0.001, 0.0)
        rounds.add(rec)
    kept = [r.round for r in rounds.recent()]
    assert kept == list(range(101, rounds.CAPACITY + 101))
    assert rounds.rounds == rounds.CAPACITY + 100 and rounds.CAPACITY == 2048
    assert rounds.slow() == [] and rounds.summary() is None


def _rounds_of(kind, seconds, **phases):
    """A ``RoundLog`` fed ``seconds`` as rounds of ``kind``, the time in
    ``fetch`` but for what ``phases`` names for the last round."""
    rounds = tracing.RoundLog()
    for n, wall in enumerate(seconds, start=1):
        rec = tracing.RoundRecord(n, 1, 0.0, 100.0 + n, 0.0)
        last = n == len(seconds)
        for phase, s in (phases if last else {}).items():
            rec.phase_s[phase] = s
        rec.phase_s["fetch"] = wall - sum(rec.phase_s.values())
        rec.close(kind, 0, wall, 0.0)
        rounds.add(rec)
    return rounds


@pytest.mark.parametrize("last, slow", [
    (0.0199, ""),          # 4 x the median, but not 25 ms over it
    (0.031, "route"),      # both
    (0.029, ""),           # under 25 ms over
])
def test_a_round_is_slow_by_both_rules_at_once(last, slow):
    rounds = _rounds_of("decode", [0.005] * 64 + [last], route=last - 0.004)
    assert [r.slow for r in rounds.slow()] == ([slow] if slow else [])
    assert rounds.slow_rounds == (1 if slow else 0)
    if slow:
        rec, = rounds.slow()
        assert rec.median_s == pytest.approx(0.005)
        assert rounds.slow_seconds == pytest.approx(last - 0.005)
        assert "slow round 65 kind=decode 31.0 ms (median 5.0)" in rec.line()
        assert "in route" in rounds.summary()


def test_a_slow_round_is_judged_against_its_own_kind_and_after_16():
    # 40 ms is a usual admission and a slow decode round.
    rounds = _rounds_of("admit", [0.040] * 20)
    assert rounds.slow_rounds == 0
    for n in range(64):
        rec = tracing.RoundRecord(100 + n, 1, 0.0, 0.0, 0.0)
        rec.phase_s["fetch"] = 0.005
        rec.close("decode", 0, 0.005, 0.0)
        rounds.add(rec)
    rec = tracing.RoundRecord(200, 1, 0.0, 0.0, 0.0)
    rec.phase_s["idle"] = 1.0               # waiting for work is not slow
    rec.close("decode", 0, 1.005, 0.0)
    rounds.add(rec)
    assert rounds.slow_rounds == 0
    rec = tracing.RoundRecord(201, 1, 0.0, 0.0, 0.0)
    rec.close("decode", 0, 0.040, 0.0)           # under no phase at all
    rounds.add(rec)
    assert [r.slow for r in rounds.slow()] == [tracing.PHASE_OTHER]
    # A round that ran a chunk and a decode step is of kind decode too:
    # it is held to the rounds that launched as much, not to plain ones.
    for n in range(17):
        rec = tracing.RoundRecord(300 + n, 1, 0.0, 0.0, 0.0)
        rec.launched(2 * n), rec.launched(2 * n + 1)
        rec.close("decode", 0, 0.170, 0.0)
        rounds.add(rec)
    assert rounds.slow_rounds == 1
    # Before its 16th round a kind has no median, and nothing is slow.
    assert _rounds_of("verify", [0.001] * 15 + [5.0]).slow_rounds == 0
    assert _rounds_of("verify", [0.001] * 16 + [5.0]).slow_rounds == 1


def test_slow_rounds_are_logged_at_most_once_a_second(caplog):
    rounds = _rounds_of("decode", [0.005] * 64)
    with caplog.at_level("WARNING", logger=tracing.log.name):
        for n in range(3):
            rec = tracing.RoundRecord(70 + n, 1, 0.0, 0.0, 0.0)
            rec.phase_s["fetch"] = 0.2
            rec.close("decode", 0, 0.2, 0.0)
            rounds.add(rec)
            rounds.report(rec)
        assert len(caplog.records) == 1
        rounds._logged_at -= rounds.LOG_EVERY_S
        rounds.report(rec)
        rounds.report_summary()
        rounds.report_summary()              # once
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 3
    assert lines[0].startswith("slow round 70 kind=decode 200.0 ms")
    assert lines[1].endswith("(+2 slow rounds not logged)")
    assert lines[2].startswith("scheduler rounds 67, slow 3 (fetch=3)")


def test_a_256_token_request_drops_no_timeline_event(model):
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=256)
    try:
        out = d.submit([1, 2, 3], 256, request_id="long").result(timeout=120)
        assert len(out["tokens"]) == 256
        tl, = d.trace.find("long")
        assert tl["dropped_events"] == 0
        names = [e["name"] for e in tl["events"]]
        assert names == ["submit", "queued", "admitted", "prefill",
                         "first_token", "finish"]
        finish = tl["events"][-1]
        assert finish["tokens"] == 256
        # The admission's fused step gave the first token; a round each
        # for the rest, and the one that only enqueued the first plain
        # step (tokens are routed a dispatch behind: one step ahead).
        assert finish["rounds"] == 257
        # The decode phase is the one span first_token → finish.
        assert tl["spans"][-1]["name"] == "finish"
        assert sum(s["duration_ms"] for s in tl["spans"]) == pytest.approx(
            tl["duration_ms"], abs=0.01)
    finally:
        d.stop()


def test_timeline_start_is_on_the_wall_clock():
    tl = tracing.Timeline("r")
    assert abs(tl.start_wall - time.time()) < 1.0
    assert abs((time.perf_counter() - tl.start)
               - (time.time() - tl.start_wall)) < 0.01


def test_phase_counter_has_six_labels_and_lints_clean(model):
    spec, params = model
    d = ContinuousDecoder(params, spec.config, slots=2, prefill_len=16,
                          max_new_tokens=8)
    try:
        d.generate([1, 2, 3], 4, timeout=60)
        time.sleep(0.05)
        d.generate([1, 2, 3], 4, timeout=60)
        text = d.registry.render()
        assert lint(text) == []
        values = dict(re.findall(
            tracing.PHASE_COUNTER + r'\{phase="(\w+)"\} ([\d.e+-]+)', text))
        assert set(values) == set(tracing.SCHED_PHASES)
        assert all(float(v) > 0 for v in values.values()), values
        # Nothing of it in the dict snapshot: one counter, one place.
        assert not any("phase" in k for k in d.metrics())
    finally:
        d.stop()


def test_monitoring_and_debug_requests_serve_the_new_names():
    import json
    import urllib.request

    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.server import ModelServer

    server = ModelServer(EngineConfig(model="lm-test-tiny", batch_size=2,
                                      max_seq_len=16, max_new_tokens=4,
                                      decode_mode="continuous"), port=0)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        server.decoder.submit([1, 2, 3], 2, request_id="seen").result(
            timeout=60)
        with urllib.request.urlopen(
                base + "/monitoring/prometheus/metrics") as r:
            text = r.read().decode()
        assert lint(text) == []
        for phase in tracing.SCHED_PHASES:
            assert f'{tracing.PHASE_COUNTER}{{phase="{phase}"}}' in text
        with urllib.request.urlopen(base + "/debug/requests?id=seen") as r:
            tl, = json.loads(r.read())["requests"]
        at = {e["name"]: e for e in tl["events"]}
        assert at["admitted"]["round"] == at["first_token"]["round"] >= 1
        assert at["finish"]["tokens"] == 2
    finally:
        server.stop()
