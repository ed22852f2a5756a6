"""Serve driver: a ModelServer in this process, driven through
``handle_predict_stream`` (the function the REST and the gRPC handlers
both call), one client thread per request in flight, open loop.

The engine can only initialise from PRNGKey(0) or a checkpoint, so the
seed's weights are put in its place before the decoder is built from them
(``server.engine.params``); what that costs is set-up.
"""

from __future__ import annotations

import gc
import os
import statistics
import sys
import threading
import time

import numpy as np

from benchmarks.harness import device, registry, stats, traffic
from benchmarks.harness.trace import TRACE_SECONDS, TraceWindow, load
from benchmarks.reference import decoder_f32 as ref

DRAIN_S = 60.0          # how long past the close an answer is waited for
SAMPLE_REQUESTS = 4     # compared with the reference, the longest among them


class _Client(threading.Thread):
    """Sends one request and stamps each token as it arrives."""

    def __init__(self, server, model: str, index: int, request: dict,
                 due_at: float, read_timeline: bool):
        super().__init__(daemon=True)
        self.server, self.model = server, model
        self.request, self.due_at = request, due_at
        self.rid = f"bench-{index}"
        self.read_timeline = read_timeline
        self.sent_at = 0.0
        self.stamps: list[float] = []
        self.tokens: list[int] | None = None
        self.error: str | None = None
        self.queue_wait_ms: float | None = None

    def run(self) -> None:
        self.sent_at = time.perf_counter()
        body = {"instances": [{
            "tokens": self.request["tokens"],
            "max_new_tokens": self.request["max_new_tokens"]}]}
        try:
            for record in self.server.handle_predict_stream(
                    self.model, body, request_id=self.rid):
                if record.get("done"):
                    self.tokens = list(record["tokens"])
                else:
                    self.stamps.append(time.perf_counter())
            if self.read_timeline:
                for tl in self.server.decoder.trace.find(self.rid):
                    at = {e["name"]: e["t_ms"] for e in tl["events"]}
                    if "queued" in at and "admitted" in at:
                        self.queue_wait_ms = at["admitted"] - at["queued"]
        except Exception as e:  # a failed request is counted, not raised
            self.error = f"{type(e).__name__}: {e}"
        finally:
            self.server = None  # so that the driver can free the program


def _warm_admissions(decoder, counter) -> dict:
    """Drive every (admission size, prompt width) bucket once. The
    decoder's own warm() admits one request per prompt width; admission
    batches are bucketed by size too, and the traffic reaches those. The
    requests of one burst are queued under the scheduler's own condition
    so that it admits them together."""
    before = counter.snapshot()
    took = []
    floor = (decoder.prefill_len >> decoder.prefill_len_buckets
             if decoder.prefill_len_buckets else decoder.prefill_len)
    widths = []
    w = max(1, floor)
    while w < decoder.prefill_len:
        widths.append(w)
        w *= 2
    widths.append(decoder.prefill_len)
    size = 1
    while True:
        for width in widths:
            prompt = ([5, 9, 14] * (width // 3 + 1))[:width]
            t = time.perf_counter()
            with decoder._cv:
                handles = [decoder.submit(prompt, 2) for _ in range(size)]
            for h in handles:
                h.result(timeout=600)
            took.append(round(time.perf_counter() - t, 2))
        if size >= decoder.slots:
            break
        size = min(size * 2, decoder.slots)
    after = counter.snapshot()
    return {**{k: after[k] - before[k] for k in ("compiles", "cache_hits",
                                                 "cache_misses")},
            "burst_s": took}


def _sample(done: list, seed: int) -> list:
    """The longest finished request and a few more drawn from the seed."""
    if not done:
        return []
    longest = max(done, key=lambda c: len(c.request["tokens"]) + len(c.tokens))
    rest = [c for c in done if c is not longest]
    rng = np.random.default_rng(seed)
    picks = rng.choice(len(rest), min(SAMPLE_REQUESTS - 1, len(rest)),
                       replace=False) if rest else []
    return [longest] + [rest[i] for i in picks]


def served_gaps(seed: int, widths, sample: list, mode: str = "f32",
                lower: str | None = None) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``sample`` (pairs of
    prompt and served tokens). With ``lower`` the served tokens are not
    judged: at each position the token that precision puts first is."""
    import jax.numpy as jnp

    n = len(sample)
    longest = max(len(p) + len(o) for p, o in sample)
    length = -(-longest // 128) * 128
    n_out = max(len(o) for _, o in sample)
    tokens = np.zeros((n, length), np.int32)
    positions = np.zeros((n, n_out), np.int32)
    served = np.zeros((n, n_out), np.int32)
    valid = np.zeros((n, n_out), bool)
    for i, (prompt, out) in enumerate(sample):
        seq = list(prompt) + list(out)
        tokens[i, :len(seq)] = seq
        # Served token j was chosen from the logits at position
        # len(prompt) - 1 + j.
        positions[i, :len(out)] = len(prompt) - 1 + np.arange(len(out))
        served[i, :len(out)] = out
        valid[i, :len(out)] = True
    logits = ref.logits_at(seed, widths, tokens, positions, mode)
    judged = jnp.asarray(served)
    if lower is not None:
        judged = jnp.argmax(ref.logits_at(seed, widths, tokens, positions,
                                          lower), axis=-1)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, judged[..., None], -1)[..., 0]
    gaps = np.where(valid, np.asarray(best - got), 0.0)
    return {"widest_gap": float(gaps.max()),
            "served_tokens": int(valid.sum()),
            "mismatches": int((gaps > 0).sum())}


def run(cell: dict, seed: int, seconds: float, trace: bool, t0: float,
        counter, control: bool = False) -> dict:
    import jax

    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.server import ModelServer

    config, mix = cell["config_file"], cell["traffic_file"]
    widths = ref.Widths.from_config(config)
    name = registry.register_preset(config)
    server = ModelServer(EngineConfig(model=name, **config["engine"]),
                         grpc_port=None)
    t_engine = time.perf_counter() - t0
    old = server.engine.params
    server.engine.params = None
    weights = registry.program_tree(ref.stacked_weights(seed, widths))
    if jax.tree.structure(weights) != jax.tree.structure(old):
        raise RuntimeError("the program's parameter tree is not the one "
                           "registry.program_tree builds")
    server.engine.params = jax.tree.map(
        lambda new, was: new.astype(was.dtype), weights, old)
    del old, weights
    decoder = server.decoder
    t_weights = time.perf_counter() - t0
    report = decoder.warm()
    if report["failed"]:
        raise RuntimeError(f"decoder.warm() failed: {report}")
    own_warm = _warm_admissions(decoder, counter)
    warm = counter.snapshot()
    print(f"set-up: engine built at {t_engine:.1f} s, the seed's weights "
          f"and the decoder at {t_weights:.1f} s, decoder.warm() "
          f"{report['seconds']:.1f} s, all warm at "
          f"{time.perf_counter() - t0:.1f} s; the harness's admission-size "
          f"bursts paid {own_warm}; compiles so far {warm}",
          file=sys.stderr, flush=True)

    schedule = getattr(traffic, mix["generator"])(
        mix, seed, seconds, config["vocab_size"])
    tracer = None
    if trace:
        tracer = TraceWindow(
            os.path.join(device.OUT_DIR, "trace", cell["name"]),
            delay=min(seconds / 3, 8.0),
            seconds=min(TRACE_SECONDS, seconds / 2),
            snapshot=decoder.metrics)
        tracer.start()
    before = decoder.metrics()
    setup_s = time.perf_counter() - t0
    t_start = time.perf_counter()
    clients = []
    for i, request in enumerate(schedule):
        due_at = t_start + request["due"]
        wait = due_at - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        clients.append(_Client(server, name, i, request, due_at, trace))
        clients[-1].start()
    wait = t_start + seconds - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    t_close = t_start + seconds
    after = decoder.metrics()
    for c in clients:
        c.join(max(0.0, t_close + DRAIN_S - time.perf_counter()))
    t_drained = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    in_window = counter.snapshot()["compiles"] - warm["compiles"]
    peak = device.memory_peak_bytes(cell["chips"])

    done = [c for c in clients if c.tokens is not None and not c.is_alive()]
    failed = len(clients) - len(done)
    ttft = [1e3 * (c.stamps[0] - c.due_at) for c in clients if c.stamps]
    gaps = [1e3 * (b - a) for c in clients
            for a, b in zip(c.stamps, c.stamps[1:])]
    in_window_tokens = sum(1 for c in clients for s in c.stamps
                           if s <= t_close)
    late = [1e3 * (c.sent_at - c.due_at) for c in clients]
    errors = sorted({c.error for c in clients if c.error})[:3]

    # ---- free the program, then the reference ---------------------------
    sample = [(c.request["tokens"], c.tokens) for c in _sample(done, seed)]
    live_context = sum(
        len(c.request["tokens"]) + len(c.tokens) / 2 for c in done) / max(
            1, len(done))
    queue_waits = [c.queue_wait_ms for c in clients
                   if c.queue_wait_ms is not None]
    decoder.stop()
    del decoder, server
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    if sample:
        compared = served_gaps(seed, widths, sample)
    else:
        compared = {"widest_gap": stats.MISSING, "served_tokens": 0,
                    "mismatches": 0}
    numbers = {"served_logit_gap": compared["widest_gap"],
               "unanswered": float(failed)}
    print(f"reference: {time.perf_counter() - t_ref:.1f} s over "
          f"{len(sample)} requests, {compared['served_tokens']} served "
          f"tokens, {compared['mismatches']} not the reference's first",
          file=sys.stderr, flush=True)

    planted = None
    if control and sample:
        # The control: at the same prompts and tokens, the gap of the
        # token that int8 (the precision below the stated bf16) puts
        # first; bf16 beside it shows what the stated precision reads.
        planted = {m: served_gaps(seed, widths, sample, lower=m)
                   for m in ("int8", "bf16")}
    counted = ("decode_steps", "prefill_dispatches", "prefill_tokens",
               "tokens_emitted", "requests_admitted")
    counters = {k: after[k] - before[k] for k in counted}
    traced = ({k: tracer.marks[1][k] - tracer.marks[0][k] for k in counted}
              if tracer is not None and tracer.marks else None)
    reduced = load(tracer.dir, cell["chips"], tracer.window_s) \
        if tracer is not None else None
    n = len(clients)
    return {
        "control": planted,
        "attempted": n, "failed": failed,
        "compiles_in_window": in_window,
        "memory_peak_bytes": peak,
        "end_to_end": {
            "itl_p95_ms": stats.percentile(gaps, 95) if gaps
            else stats.MISSING,
            "serve_tokens_per_s": in_window_tokens / seconds,
            "setup_s": setup_s},
        "numbers": numbers, "notes": {}, "limits": config["limits"],
        "earlier": {
            "requests": n, "finished": len(done), "errors": errors,
            "ttft_p50_ms": statistics.median(ttft) if ttft else None,
            "ttft_p95_ms": stats.percentile(ttft, 95, n - len(ttft)),
            "itl_p50_ms": statistics.median(gaps) if gaps else None,
            "completed_per_s": sum(
                1 for c in done if c.stamps[-1] <= t_close) / seconds,
            "generator_lateness_p95_ms": stats.percentile(late, 95),
            "drain_s": t_drained - t_close,
            "peak_in_flight": after["peak_in_flight"],
            "counters": counters},
        "run": {"kind": "serve", "config": config, "mix": mix,
                "counters": counters, "slots": config["engine"]["batch_size"],
                "queue_wait_ms": queue_waits, "live_context": live_context,
                "ttft_ms": ttft, "ttft_missing": n - len(ttft),
                "prompt_tokens": counters["prefill_tokens"],
                "window_s": seconds, "trace": reduced,
                "trace_counters": traced},
    }
