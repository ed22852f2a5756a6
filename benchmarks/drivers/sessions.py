"""Sessions driver: a closed batch of long-context sessions through
``ModelServer.handle_predict_stream``, one per decode slot. Set-up submits
them all, and the server prefills them (in chunks, where the engine says
so) while the first ones already decode; the measured window opens when
every session has streamed its first token and sees decoding only, no
arrivals. A session is answered if it was still streaming at the close (its
next token came, or it had finished); then the decoder is stopped, and the
tokens a session was served inside the window are what the reference
judges.

For configurations with ``mixer_types`` (lightning and block-sparse
layers): their preset, their tree and their reference are this file's and
``reference/minicpm_sala_f32.py``'s; ``harness/registry.py`` stays
Mistral-shaped.
"""

from __future__ import annotations

import gc
import os
import statistics
import sys
import threading
import time

import numpy as np

from benchmarks.harness import device, stats, traffic
from benchmarks.harness.trace import TRACE_SECONDS, TraceWindow, load
from benchmarks.reference import minicpm_sala_f32 as ref

SAMPLE_SESSIONS = 4       # judged by the reference, the longest among them
FIRST_TOKENS_S = 1800.0   # set-up gives up on a session after this
STREAMING_S = 10.0        # "still streaming": the next token comes this soon
COUNTED = ("decode_steps", "prefill_dispatches", "prefill_tokens",
           "tokens_emitted", "requests_admitted", "sparse_tokens_attended",
           "sparse_tokens_in_context", "rows_dense", "rows_sparse")
FAULTS = {"int8": {"mode": "int8"}, "bf16": {"mode": "bf16"},
          "no_selection": {"fault": "no_selection"},
          "state_dropped": {"fault": "state_dropped"},
          "selection_bf16": {"fault": "selection_bf16"}}


class _Session(threading.Thread):
    """Sends one request; keeps each token and when it arrived."""

    def __init__(self, server, model: str, index: int, request: dict):
        super().__init__(daemon=True)
        self.server, self.model, self.request = server, model, request
        self.rid = f"session-{index}"
        self.sent_at = 0.0
        self.stamps: list[float] = []
        self.tokens: list[int] = []
        self.finished = False
        self.error: str | None = None

    def run(self) -> None:
        self.sent_at = time.perf_counter()
        body = {"instances": [{
            "tokens": self.request["tokens"],
            "max_new_tokens": self.request["max_new_tokens"]}]}
        try:
            for record in self.server.handle_predict_stream(
                    self.model, body, request_id=self.rid):
                if record.get("done"):
                    self.finished = True
                else:
                    self.tokens.append(int(record["token"]))
                    self.stamps.append(time.perf_counter())
        except Exception as e:  # counted by the driver, not raised here
            self.error = f"{type(e).__name__}: {e}"
        finally:
            self.server = None  # so that the driver can free the program


class _GcWatch:
    """The collector's passes while it is installed, by generation, and
    the seconds they held every thread of this process."""

    def __init__(self):
        self.passes = [0, 0, 0]
        self.pause_s = 0.0
        self.longest_s = 0.0
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
            return
        took = time.perf_counter() - self._t
        self.passes[info["generation"]] += 1
        self.pause_s += took
        self.longest_s = max(self.longest_s, took)


def slow_rounds(stamps: list, t_start: float, t_close: float) -> dict:
    """What one session's gaps say of the window's rounds: the longest,
    and the seconds by which the gaps over 1.25 x the median exceed it."""
    gaps = [b - a for a, b in zip(stamps, stamps[1:])
            if t_start <= a and b <= t_close]
    if not gaps:
        return {"itl_max_ms": None, "slow_rounds": 0, "slow_rounds_s": 0.0}
    mid = statistics.median(gaps)
    slow = [g - mid for g in gaps if g > 1.25 * mid]
    return {"itl_max_ms": 1e3 * max(gaps), "slow_rounds": len(slow),
            "slow_rounds_s": sum(slow)}


def still_streaming(sessions: list, t_close: float,
                    patience: float = STREAMING_S) -> list:
    """The sessions still streaming at ``t_close``: a token reaches them
    after it (a round later as a rule; a decoder that has stalled is slow,
    one that does not resume within ``patience`` has lost its sessions),
    or they had finished."""
    def waiting(s) -> bool:
        return bool(s.is_alive() and not s.finished and s.stamps
                    and s.stamps[-1] <= t_close)

    while time.perf_counter() < t_close + patience \
            and any(waiting(s) for s in sessions):
        time.sleep(0.005)
    return [s for s in sessions
            if s.stamps and (s.finished or s.stamps[-1] > t_close)]


def register_preset(config: dict) -> str:
    """The configuration as a preset of the program, from its published
    keys; a program without ``mixer_types`` fails here, at once."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import transformer

    w = ref.Widths.from_config(config)
    try:
        transformer.PRESETS[config["name"]] = transformer.TransformerConfig(
            vocab_size=w.vocab_size, d_model=w.hidden_size,
            n_layers=len(w.mixer_types), n_heads=w.num_attention_heads,
            n_kv_heads=w.num_key_value_heads, d_ff=w.intermediate_size,
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(w.rope_theta), norm_eps=w.rms_norm_eps,
            tie_embeddings=config["tie_word_embeddings"],
            dtype=jnp.dtype(config["torch_dtype"]), remat=False,
            mixer_types=w.mixer_types, embed_scale=float(w.scale_emb),
            residual_scale=w.residual_scale,
            head_scale=w.dim_model_base / w.hidden_size,
            sparse_kernel_size=w.kernel_size,
            sparse_kernel_stride=w.kernel_stride,
            sparse_block_size=w.block_size, sparse_topk=w.topk,
            sparse_init_blocks=w.init_blocks, sparse_window=w.window_size,
            sparse_dense_len=w.dense_len, **config.get("program", {}))
    except TypeError as e:
        raise SystemExit(f"this program cannot hold {config['name']}: "
                         f"{e}") from None
    return config["name"]


def install_weights(params, seed: int, w: ref.Widths):
    """The program's tree with every leaf replaced by the seed's, at the
    dtype the program held it, one layer at a time: the caller hands over
    its only reference, so an old leaf goes as the new one arrives, and
    one layer's float32 leaves are the most that is alive beside the
    tree."""
    import jax

    from kubeflow_tpu.parallel.sharding import path_str

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    del params
    paths = [path_str(kp) for kp, _ in flat]
    leaves = [leaf for _, leaf in flat]
    del flat
    outer = {"embed/kernel": "embed", "final_norm": "final_norm",
             "lm_head/kernel": "head"}
    made: tuple[int, dict] = (-1, {})
    for i, path in enumerate(paths):
        if path in outer:
            new = ref.outer_leaf(seed, w, outer[path])
        else:
            _, layer, *rest = path.split("/")
            if made[0] != int(layer):
                made = (int(layer), dict(ref.layer_weights(seed, w,
                                                           int(layer))))
            new = made[1].pop(rest[-1])
        if new.shape != leaves[i].shape:
            raise RuntimeError(f"{path}: the program holds "
                               f"{leaves[i].shape}, the reference {new.shape}")
        leaves[i] = jax.block_until_ready(new.astype(leaves[i].dtype))
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
    length = ref.padded_length(w, max(len(p) + len(o) for p, o, _ in sample))
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


def _sample(sessions: list, seed: int) -> list:
    """The longest session, the one whose first token came last (its
    window starts right behind its prefill, where a cache fault shows
    most), and more drawn from the seed."""
    fixed = [max(sessions, key=lambda c: len(c.request["tokens"]))]
    last = max(sessions, key=lambda c: c.stamps[0])
    if last is not fixed[0]:
        fixed.append(last)
    rest = [c for c in sessions if c not in fixed]
    picks = np.random.default_rng(seed).choice(
        len(rest), min(SAMPLE_SESSIONS - len(fixed), len(rest)),
        replace=False)
    return fixed + [rest[i] for i in picks]


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
    while any(not s.stamps and s.error is None and s.is_alive()
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
    for s in _sample(answered, seed) if answered else []:
        inside = [i for i, t in enumerate(s.stamps) if t_start <= t <= t_close]
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
          f"served in the window, {compared['mismatches']} not the "
          f"reference's first", file=sys.stderr, flush=True)
    planted = None
    if control and sample:
        planted = {k: served_gaps(seed, w, sample, **how)
                   for k, how in FAULTS.items()}
        # The longest session's judged queries: how often bf16 scores pick
        # other blocks than float32 ones.
        prompt, out, first = sample[0]
        row = np.zeros(ref.padded_length(w, len(prompt) + len(out)), np.int32)
        row[:len(prompt) + len(out)] = list(prompt) + list(out)
        planted["selection_flips"] = ref.selection_flips(
            seed, w, row, len(prompt) - 1 + np.arange(first, len(out)))

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
            "state_bytes": after["state_bytes"],
            "kv_bytes_in_use": after["kv_bytes_in_use"],
            "weights_bytes": after["weights_bytes"],
            "peak_in_flight": after["peak_in_flight"],
            "counters": counters},
        # What the serve cells' readers index, so that an unlisted workload
        # (every reader is tried) runs them too: no queue, no arrivals.
        "run": {"kind": "serve", "config": config, "mix": mix,
                "counters": counters, "slots": config["engine"]["batch_size"],
                "queue_wait_ms": [], "ttft_ms": ttft,
                "ttft_missing": len(sessions) - len(ttft),
                "live_context": counters["sparse_tokens_in_context"]
                / max(1, counters["tokens_emitted"]),
                "prompt_tokens": counters["prefill_tokens"],
                "window_s": seconds, "trace": reduced,
                "trace_counters": traced},
    }
