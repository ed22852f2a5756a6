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
