"""Looped-stack driver: a closed batch of reasoning sessions through
``ModelServer.handle_predict_stream``, one per decode slot, for a
configuration whose stack runs ``total_ut_steps`` times a token
(``reference/ouro_f32.py``). Set-up submits them all; the measured window
opens when every session has streamed its first token and the first plain
decode step after the last admission has been served (so that its module
is compiled), and sees decoding only, all rows live, one step a dispatch. A session is answered if it was
still streaming at the close (``drivers/sessions.py:still_streaming``);
then the decoder is stopped, and a FIXED count of tokens a session — the
first ``judged_tokens`` of the traffic file that it was served inside the
window — is what the reference judges, of every session (a maximum over
"every token of the window" reads higher the faster the step is:
PERF.md §7).

The preset, the tree and the reference of a looped configuration are this
file's and ``reference/ouro_f32.py``'s; the session threads, the
collector's watch and the rule for ``unanswered`` are
``drivers/sessions.py``'s.
"""

from __future__ import annotations

import gc
import os
import statistics
import sys
import time

import numpy as np

from benchmarks.drivers.sessions import (
    FIRST_TOKENS_S,
    _GcWatch,
    _Session,
    slow_rounds,
    still_streaming,
)
from benchmarks.harness import device, stats, traffic
from benchmarks.harness.trace import TRACE_SECONDS, TraceWindow, load
from benchmarks.reference import ouro_f32 as ref

COUNTED = ("decode_steps", "prefill_dispatches", "prefill_tokens",
           "tokens_emitted", "requests_admitted", "loop_passes",
           "kv_tokens_attended")
FAULTS = {"int8": {"mode": "int8"}, "bf16": {"mode": "bf16"},
          "three_passes": {"fault": "three_passes"},
          "shared_cache": {"fault": "shared_cache"},
          "no_loop_norm": {"fault": "no_loop_norm"}}
# The program's tree -> the reference's leaf names.
OUTER = {"embed/kernel": "embed", "final_norm": "final_norm",
         "lm_head/kernel": "head", "exit_gate/kernel": "exit_w",
         "exit_gate/bias": "exit_b"}


def register_preset(config: dict) -> str:
    """The configuration as a preset of the program, from its published
    keys; a program that cannot loop its stack fails here, at once, and
    says which field it lacks."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import transformer

    w = ref.Widths.from_config(config)
    if w.num_attention_heads * w.head_dim != w.hidden_size:
        raise ValueError("the program derives head_dim from hidden_size")
    try:
        transformer.PRESETS[config["name"]] = transformer.TransformerConfig(
            vocab_size=w.vocab_size, d_model=w.hidden_size,
            n_layers=w.num_hidden_layers, n_heads=w.num_attention_heads,
            n_kv_heads=w.num_key_value_heads, d_ff=w.intermediate_size,
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(w.rope_theta), norm_eps=w.rms_norm_eps,
            tie_embeddings=config["tie_word_embeddings"],
            dtype=jnp.dtype(config["torch_dtype"]), remat=False,
            n_passes=w.total_ut_steps, post_norms=True,
            exit_threshold=float(w.early_exit_threshold),
            **config.get("program", {}))
    except TypeError as e:
        raise SystemExit(f"this program cannot hold {config['name']}: "
                         f"{e}") from None
    return config["name"]


def install_weights(params, seed: int, w: ref.Widths):
    """The program's tree with every leaf replaced by the seed's, at the
    dtype the program held it, a leaf at a time: the caller hands over its
    only reference, so an old leaf goes as the new one arrives. A layer
    leaf is stacked from its layers' float32 draws, each cast as it is
    made, so one stacked leaf twice over (1.1 GB each at the published
    sizes) is the most that is alive beside the tree."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.parallel.sharding import path_str

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    del params
    paths = [path_str(kp) for kp, _ in flat]
    leaves = [leaf for _, leaf in flat]
    del flat
    for i, path in enumerate(paths):
        dtype = leaves[i].dtype
        if path in OUTER:
            new = ref.outer_leaf(seed, w, OUTER[path]).astype(dtype)
        else:
            name = path.split("/")[-1]
            new = jnp.stack([ref.layer_leaf(seed, w, layer, name).astype(dtype)
                             for layer in range(w.num_hidden_layers)])
        if new.shape != leaves[i].shape:
            raise RuntimeError(f"{path}: the program holds "
                               f"{leaves[i].shape}, the reference {new.shape}")
        leaves[i] = jax.block_until_ready(new)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def served_gaps(seed: int, w: ref.Widths, sample: list, *,
                mode: str = "f32", fault: str | None = None) -> dict:
    """The widest gap by which a judged token's logit lies below the
    reference's best. ``sample``: (prompt, served tokens, index of the
    first judged one). As called, the served tokens are judged; with a
    lower ``mode`` or a ``fault``, the tokens that variant of the
    reference puts first are, at the same positions."""
    import jax.numpy as jnp

    n = len(sample)
    length = max(len(p) + len(o) for p, o, _ in sample)
    n_out = max(len(o) - first for _, o, first in sample)
    tokens = np.zeros((n, length), np.int32)
    positions = np.zeros((n, n_out), np.int32)
    served = np.zeros((n, n_out), np.int32)
    valid = np.zeros((n, n_out), bool)
    for i, (prompt, out, first) in enumerate(sample):
        seq = list(prompt) + list(out)
        tokens[i, :len(seq)] = seq
        # Served token j was chosen from the logits at position
        # len(prompt) - 1 + j.
        m = len(out) - first
        positions[i, :m] = len(prompt) - 1 + first + np.arange(m)
        served[i, :m] = out[first:]
        valid[i, :m] = True
    logits = ref.logits_at(seed, w, tokens, positions)
    judged = jnp.asarray(served)
    if mode != "f32" or fault is not None:
        judged = jnp.argmax(ref.logits_at(
            seed, w, tokens, positions, mode, fault,
            np.array([len(p) for p, _, _ in sample], np.int32)), axis=-1)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, judged[..., None], -1)[..., 0]
    gaps = np.where(valid, np.asarray(best - got), 0.0)
    return {"widest_gap": float(gaps.max()),
            "served_tokens": int(valid.sum()),
            "mismatches": int((gaps > 0).sum())}


def run(cell: dict, seed: int, seconds: float, trace: bool, t0: float,
        counter, control: bool = False) -> dict:
    import jax

    config, mix = cell["config_file"], cell["traffic_file"]
    w = ref.Widths.from_config(config)
    name = register_preset(config)

    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.server import ModelServer

    server = ModelServer(EngineConfig(model=name, **config["engine"]),
                         grpc_port=None)
    t_engine = time.perf_counter() - t0
    held, server.engine.params = server.engine.params, None
    server.engine.params = install_weights(held, seed, w)
    del held
    decoder = server.decoder
    t_weights = time.perf_counter() - t0

    # One second of the mix's backlog is the batch: every session is due
    # at the start, the window's length changes nothing about them.
    schedule = getattr(traffic, mix["generator"])(
        mix, seed, 1.0, config["vocab_size"])
    sessions = [_Session(server, name, i, request)
                for i, request in enumerate(schedule)]
    t_submit = time.perf_counter()
    for s in sessions:
        s.start()
    # Every admission is a fused prefill + step of one row, so the first
    # PLAIN decode step runs (and compiles) only after the last of them:
    # a session's second token says it has.
    while any(len(s.stamps) < 2 and s.error is None and s.is_alive()
              for s in sessions):
        if time.perf_counter() - t_submit > FIRST_TOKENS_S:
            break
        time.sleep(0.05)
    t_all_first = time.perf_counter()
    prompt_tokens = sum(len(s.request["tokens"]) for s in sessions)
    warm = counter.snapshot()
    print(f"set-up: engine built at {t_engine:.1f} s, the seed's weights "
          f"and the decoder at {t_weights:.1f} s, {len(sessions)} sessions "
          f"({prompt_tokens} prompt tokens) all at their first token "
          f"{t_all_first - t_submit:.1f} s after they were sent; compiles "
          f"so far {warm}", file=sys.stderr, flush=True)

    tracer = None
    if trace:
        tracer = TraceWindow(
            os.path.join(device.OUT_DIR, "trace", cell["name"]),
            delay=min(seconds / 3, 8.0),
            seconds=min(TRACE_SECONDS, seconds / 2),
            snapshot=decoder.metrics)
        tracer.start()
    collector = _GcWatch()
    gc.callbacks.append(collector)
    t_start = time.perf_counter()
    before = decoder.metrics()
    setup_s = t_start - t0
    t_close = t_start + seconds
    time.sleep(seconds)
    after = decoder.metrics()
    gc.callbacks.remove(collector)
    answered = still_streaming(sessions, t_close)
    marks, trace_dir, traced_s = None, None, 0.0
    if tracer is not None:
        tracer.finish()
        marks, trace_dir, traced_s = tracer.marks, tracer.dir, tracer.window_s
        del tracer  # its snapshot is the decoder's method: let both go
    in_window = counter.snapshot()["compiles"] - warm["compiles"]
    peak = device.memory_peak_bytes(cell["chips"])
    decoder.stop()
    for s in sessions:
        s.join(10.0)

    failed = len(sessions) - len(answered)
    gaps = [1e3 * (b - a) for s in sessions
            for a, b in zip(s.stamps, s.stamps[1:])
            if t_start <= a and b <= t_close]
    in_window_tokens = sum(1 for s in sessions for t in s.stamps
                           if t_start <= t <= t_close)
    errors = sorted({s.error for s in sessions
                     if s.error and "decoder stopped" not in s.error})[:3]
    ttft = [1e3 * (s.stamps[0] - s.sent_at) for s in sessions if s.stamps]

    # ---- free the program, then the reference ---------------------------
    sample = []
    for s in answered:
        inside = [i for i, t in enumerate(s.stamps)
                  if t_start <= t <= t_close][:mix["judged_tokens"]]
        if inside:
            sample.append((s.request["tokens"], s.tokens[:inside[-1] + 1],
                           inside[0]))
    del decoder, server
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    compared = served_gaps(seed, w, sample) if sample else {
        "widest_gap": stats.MISSING, "served_tokens": 0, "mismatches": 0}
    numbers = {"served_logit_gap": compared["widest_gap"],
               "unanswered": float(failed)}
    print(f"reference: {time.perf_counter() - t_ref:.1f} s over "
          f"{len(sample)} sessions, {compared['served_tokens']} tokens "
          f"judged (the first {mix['judged_tokens']} a session served in "
          f"the window), {compared['mismatches']} not the reference's "
          "first", file=sys.stderr, flush=True)
    planted = None
    if control and sample:
        planted = {k: served_gaps(seed, w, sample, **how)
                   for k, how in FAULTS.items()}

    counters = {k: after[k] - before[k] for k in COUNTED}
    traced = ({k: marks[1][k] - marks[0][k] for k in COUNTED}
              if marks else None)
    reduced = load(trace_dir, cell["chips"], traced_s) if trace_dir else None
    return {
        "control": planted,
        "attempted": len(sessions), "failed": failed,
        "compiles_in_window": in_window,
        "memory_peak_bytes": peak,
        "end_to_end": {
            "itl_p95_ms": stats.percentile(gaps, 95) if gaps
            else stats.MISSING,
            "serve_tokens_per_s": in_window_tokens / seconds,
            "setup_s": setup_s},
        "numbers": numbers, "notes": {}, "limits": config["limits"],
        "earlier": {
            "sessions": len(sessions), "streaming_at_close": len(answered),
            "errors": errors,
            "judged_tokens": compared["served_tokens"],
            "setup_prefill_tokens_per_s":
                prompt_tokens / (t_all_first - t_submit),
            "ttft_p50_ms": statistics.median(ttft) if ttft else None,
            "itl_p50_ms": statistics.median(gaps) if gaps else None,
            **slow_rounds(sessions[0].stamps, t_start, t_close),
            "gc_passes": collector.passes,
            "gc_pause_ms": 1e3 * collector.pause_s,
            "gc_longest_ms": 1e3 * collector.longest_s,
            "tokens_before_window_max": max(
                (sum(1 for t in s.stamps if t < t_start) for s in sessions),
                default=0),
            "cache_layers": after["cache_layers"],
            "kv_bytes_per_token": after["kv_bytes_per_token"],
            "weights_bytes": after["weights_bytes"],
            "peak_in_flight": after["peak_in_flight"],
            "counters": counters},
        # What the serve cells' readers index, so that an unlisted workload
        # (every reader is tried) runs them too: no queue, no arrivals.
        "run": {"kind": "serve", "config": config, "mix": mix,
                "counters": counters, "slots": config["engine"]["batch_size"],
                "queue_wait_ms": [], "ttft_ms": ttft,
                "ttft_missing": len(sessions) - len(ttft),
                "live_context": counters["kv_tokens_attended"]
                / max(1, counters["tokens_emitted"]),
                "prompt_tokens": counters["prefill_tokens"],
                "window_s": seconds, "trace": reduced,
                "trace_counters": traced},
    }
