"""Latent-attention expert driver: a closed batch of generation sessions
through ``ModelServer.handle_predict_stream``, one per decode slot, for a
configuration with latent attention and sigmoid-routed experts
(``reference/kanana_f32.py``). Set-up submits them all; the measured window
opens when every session has streamed its first token and the first plain
decode step after the last admission has been served (so that its module is
compiled), and sees decoding only, all rows live, one step a dispatch. A
session is answered if it was still streaming at the close
(``drivers/sessions.py:still_streaming``); then the decoder is stopped, and
a FIXED count of tokens — the first ``judged_tokens`` of the traffic file
that each of ``judged_sessions`` sessions was served inside the window (the
longest prompt, the last admitted, the rest drawn from the seed) — is what
the reference judges.

The preset, the tree and the reference of such a configuration are this
file's and ``reference/kanana_f32.py``'s; the session threads, the
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
from benchmarks.reference import kanana_f32 as ref

COUNTED = ("decode_steps", "prefill_dispatches", "prefill_tokens",
           "tokens_emitted", "requests_admitted", "kv_tokens_attended")
# In the order they are read: each costs a pass of the reference.
FAULTS = {"int8": {"mode": "int8"}, "bf16": {"mode": "bf16"},
          "capacity": {"fault": "capacity"},
          "no_shared": {"fault": "no_shared"},
          "softmax_routing": {"fault": "softmax_routing"},
          "no_routed_scaling": {"fault": "no_routed_scaling"},
          "no_kv_norm": {"fault": "no_kv_norm"}}
# The program's tree -> the reference's leaf names.
OUTER = {"embed/kernel": "embed", "final_norm": "final_norm",
         "lm_head/kernel": "head"}
EXPERTS = ("gate", "up", "down")


def register_preset(config: dict) -> str:
    """The configuration as a preset of the program, from its published
    keys; a program without latent attention fails here, at once, and
    says which field it lacks."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import transformer

    w = ref.Widths.from_config(config)
    if config["scoring_func"] != "sigmoid" or config["topk_method"] \
            != "noaux_tc" or config["n_group"] != 1 \
            or config["q_lora_rank"] is not None:
        raise ValueError("the program routes by sigmoid over one group "
                         "(noaux_tc) and projects q without compression")
    try:
        transformer.PRESETS[config["name"]] = transformer.TransformerConfig(
            vocab_size=w.vocab_size, d_model=w.hidden_size,
            n_layers=w.num_hidden_layers, n_heads=w.num_attention_heads,
            n_kv_heads=w.num_attention_heads, d_ff=w.intermediate_size,
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(w.rope_theta), norm_eps=w.rms_norm_eps,
            tie_embeddings=config["tie_word_embeddings"],
            dtype=jnp.dtype(config["torch_dtype"]), remat=False,
            kv_lora_rank=w.kv_lora_rank,
            qk_nope_head_dim=w.qk_nope_head_dim,
            qk_rope_head_dim=w.qk_rope_head_dim, v_head_dim=w.v_head_dim,
            n_experts=w.n_routed_experts,
            expert_top_k=w.num_experts_per_tok,
            routed_scaling=float(w.routed_scaling_factor),
            n_shared_experts=w.n_shared_experts,
            d_expert=w.moe_intermediate_size,
            first_k_dense=w.first_k_dense_replace,
            **config.get("program", {}))
    except TypeError as e:
        raise SystemExit(f"this program cannot hold {config['name']}: "
                         f"{e}") from None
    return config["name"]


def program_leaf(seed: int, w: ref.Widths, path: str, layers: range,
                 dtype):
    """The program's leaf at ``path`` (its checkpoint names) from the
    seed's reference weights, stacked over ``layers``, at ``dtype``
    (each piece cast as it is drawn): the up-projection W_kvb split into
    a head's ``w_uk`` [nope, rank] and ``w_uv`` [rank, v], the rope
    columns of W_q and W_kva de-interleaved (``ref.rope_columns``), a
    routed expert matrix stacked over the experts."""
    import jax.numpy as jnp

    name = path.split("/")[-1]
    h, nope, v = w.num_attention_heads, w.qk_nope_head_dim, w.v_head_dim
    q_cols, kv_cols = ref.rope_columns(w)

    def one(layer):
        if path.endswith("mlp/" + name) and name in EXPERTS \
                and w.is_moe(layer):
            return ref.expert_leaves(seed, w, layer, name).astype(dtype)
        if name in ("w_uk", "w_uv"):
            kvb = ref.layer_leaf(seed, w, layer, "wkv_b").reshape(
                w.kv_lora_rank, h, nope + v)
            if name == "w_uk":
                return kvb[..., :nope].transpose(1, 2, 0).astype(dtype)
            return kvb[..., nope:].transpose(1, 0, 2).astype(dtype)
        leaf = ref.layer_leaf(seed, w, layer, name)
        if name == "wq":
            leaf = leaf[:, q_cols]
        elif name == "wkv_a":
            leaf = leaf[:, kv_cols]
        return leaf.astype(dtype)

    # Each layer's draw goes to the host as it is made and the stack comes
    # back whole: the chip holds one layer's draw beside the tree, never a
    # stacked leaf twice (a routed-expert leaf is 1.88 GiB at the
    # published sizes).
    return jnp.asarray(np.stack([np.asarray(one(layer)) for layer in layers]))


def install_weights(params, seed: int, w: ref.Widths):
    """A tree of the structure, shapes and dtypes of ``params`` (the
    program's tree, or ``jax.eval_shape`` of it) holding the seed's
    weights, built a leaf at a time."""
    import jax

    from kubeflow_tpu.parallel.sharding import path_str

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    dense = range(w.first_k_dense_replace)
    moe = range(w.first_k_dense_replace, w.num_hidden_layers)
    leaves = []
    for key_path, like in flat:
        path = path_str(key_path)
        if path in OUTER:
            new = ref.outer_leaf(seed, w, OUTER[path]).astype(like.dtype)
        else:
            layers = dense if path.startswith("dense_layers/") else moe
            new = program_leaf(seed, w, path, layers, like.dtype)
        if new.shape != like.shape:
            raise RuntimeError(f"{path}: the program holds "
                               f"{like.shape}, the reference {new.shape}")
        leaves.append(jax.block_until_ready(new))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def served_gaps(seed: int, w: ref.Widths, sample: list, *,
                controls: dict | None = None) -> dict:
    """The widest gap by which a judged token's logit lies below the
    reference's best, and the share of judged tokens that are not its
    first. ``sample``: (prompt, served tokens, index of the first judged
    one). The served tokens are judged; with ``controls`` ({name:
    ``ref.logits_at``'s mode and fault}, as :data:`FAULTS`), so are the
    tokens each such variant of the reference puts first at the same
    positions, against the same sound reference (under "control", each
    printed as it is read)."""
    n = len(sample)
    lengths = np.array([len(p) + len(o) for p, o, _ in sample], np.int32)
    n_out = max(len(o) - first for _, o, first in sample)
    # Padded to whole buckets, so that another seed's sample (a few tokens
    # longer or shorter) reuses the reference's compiled steps; a pad
    # token follows every real one and no real token sees it.
    width = -(-int(lengths.max()) // ref.ROWS_BUCKET) * ref.ROWS_BUCKET
    tokens = np.zeros((n, width), np.int32)
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
    ties = []
    logits = np.asarray(ref.logits_at(seed, w, tokens, positions,
                                      lengths=lengths, ties=ties))
    best = logits.max(axis=-1)

    def reading(judged):
        got = np.take_along_axis(logits, judged[..., None], -1)[..., 0]
        gaps = np.where(valid, best - got, 0.0)
        mismatches = int((gaps > 0).sum())
        return {"widest_gap": float(gaps.max()),
                "served_tokens": int(valid.sum()),
                "mismatches": mismatches,
                "mismatch_pct": 100.0 * mismatches / int(valid.sum())}

    out = {**reading(served),
           "near_ties": [sum(n for n, _ in ties), sum(m for _, m in ties)]}
    if controls is not None:
        out["control"] = {}
        for name, how in controls.items():
            out["control"][name] = reading(np.asarray(ref.logits_at(
                seed, w, tokens, positions, how.get("mode", "f32"),
                how.get("fault"), lengths)).argmax(axis=-1))
            print(f"control {name}: {out['control'][name]}",
                  file=sys.stderr, flush=True)
    return out


def judged_sessions(sessions: list, answered: list, count: int,
                    seed: int) -> list:
    """Whom the reference judges: the answered session with the longest
    prompt, the last admitted (its first token came last), and the rest
    drawn from the seed among the others."""
    if not answered:
        return []
    picked = [max(answered, key=lambda s: len(s.request["tokens"]))]
    last = max(answered, key=lambda s: s.stamps[0])
    if last not in picked:
        picked.append(last)
    rest = [s for s in answered if s not in picked]
    draw = np.random.default_rng(seed).permutation(len(rest))
    picked += [rest[i] for i in draw[:max(0, count - len(picked))]]
    return sorted(picked, key=sessions.index)


def _counts(m: dict) -> dict:
    """The counters of one metrics() snapshot this driver differences:
    COUNTED, and the device's routing counts summed (experts touched over
    every expert layer; tokens routed)."""
    out = {k: m[k] for k in COUNTED}
    out["experts_touched"] = int(sum(m.get("moe_touched", ())))
    out["tokens_routed"] = int(np.sum(m.get("moe_routed", 0)))
    return out


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
    # The seed's tree is built to the shapes of the engine's own draw,
    # which goes first: the two (7.58 GB each at the cut's sizes) never
    # share the chip.
    shapes = jax.eval_shape(lambda: server.engine.params)
    server.engine.params = None
    gc.collect()
    server.engine.params = install_weights(shapes, seed, w)
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
    # The first metrics() compiles the copies that fetch the device's
    # routing counts: in set-up, not in the window's first snapshot.
    decoder.metrics()
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
    routed = (np.asarray(after["moe_routed"])
              - np.asarray(before["moe_routed"]))
    load_ratio = [float(r.max() / r.mean()) if r.sum() else None
                  for r in routed]

    print(f"window: itl_p95_ms "
          f"{stats.percentile(gaps, 95) if gaps else stats.MISSING:.4f}, "
          f"serve_tokens_per_s {in_window_tokens / seconds:.2f}",
          file=sys.stderr, flush=True)

    # ---- free the program, then the reference ---------------------------
    sample = []
    for s in judged_sessions(sessions, answered, mix["judged_sessions"],
                             seed):
        inside = [i for i, t in enumerate(s.stamps)
                  if t_start <= t <= t_close][:mix["judged_tokens"]]
        if inside:
            sample.append((s.request["tokens"], s.tokens[:inside[-1] + 1],
                           inside[0]))
    del decoder, server
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    compared = served_gaps(
        seed, w, sample, controls=FAULTS if control else None) if sample \
        else {"widest_gap": stats.MISSING, "served_tokens": 0,
              "mismatches": 0, "mismatch_pct": stats.MISSING,
              "near_ties": None}
    planted = compared.pop("control", None)
    # The widest gap is one token's, and at bfloat16 a near tie between the
    # two best logits flips now and then: over 1,024 tokens the widest flip
    # can read near what int8 operands read. The share of judged tokens
    # that are not the reference's first counts every token and parts the
    # two far more widely (PERF.md, section 2).
    numbers = {"served_logit_gap": compared["widest_gap"],
               "served_mismatch_pct": compared["mismatch_pct"],
               "unanswered": float(failed)}
    print(f"reference: {time.perf_counter() - t_ref:.1f} s over "
          f"{len(sample)} sessions, {compared['served_tokens']} tokens "
          f"judged (the first {mix['judged_tokens']} a session served in "
          f"the window), {compared['mismatches']} not the reference's "
          f"first, widest gap {compared['widest_gap']:.4f}",
          file=sys.stderr, flush=True)
    counters = {k: v - _counts(before)[k] for k, v in _counts(after).items()}
    traced = ({k: v - _counts(marks[0])[k]
               for k, v in _counts(marks[1]).items()} if marks else None)
    reduced = load(trace_dir, cell["chips"], traced_s) if trace_dir else None
    steps = max(1, counters["decode_steps"])
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
            "judged_mismatches": compared["mismatches"],
            "routing_near_ties": compared["near_ties"],
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
            "expert_load_max_over_mean": load_ratio,
            "experts_touched_per_layer_step": counters["experts_touched"]
            / (steps * len(routed)) if len(routed) else None,
            "cache_layers": after["cache_layers"],
            "kv_bytes_per_token": after["kv_bytes_per_token"],
            "dense_attn_impl": after["dense_attn_impl"],
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
