"""Counts and trace readings for configurations with latent attention and
sigmoid-routed experts (``kv_lora_rank``, ``n_routed_experts``): the
operations and bytes a decode step needs, from the published widths and
from what the decoder counted, and the device time under the scopes such a
model sets.

Least bytes of the traced decode steps, at bf16 (the router and its bias
float32): every matrix outside the routed experts once a step (attention,
the dense layers, the shared experts, the router, the head), the routed
experts the step touched once each (``experts_touched``: summed over steps
and expert layers, from the device's own count), and the latent row of
every attended token in every layer (``kv_tokens_attended``: per emitted
token, its row's length), ``kv_lora_rank + qk_rope_head_dim`` values a
token and layer. Nothing else: not the zeros that pad the store's row to
its tile, no row past its live tokens, no embedding row, no norm gain.
"""

from __future__ import annotations

import functools
import importlib.util

from benchmarks.harness import spans

# The program's names (kubeflow_tpu/observability/tracing.py:
# LATENT_MOE_SCOPES), quoted and not imported, as spans.DEVICE_SCOPES
# quotes the others.
LATENT_MOE_SCOPES = ("latent_attn", "moe_route", "moe_experts")
DECODE_MODULE = r"^jit_decode_(step|chunk)$"
BF16, F32 = 2, 4
# XLA:TPU lowers ``lax.ragged_dot`` to Mosaic custom calls whose op_name is
# their own ("ragged-dot-none", "ragged-dot-metadata") and holds no scope
# path, so spans.py books them to no scope. In a window of decode steps
# they are the grouped products under ``moe_experts``: the step holds no
# other ragged dot.
RAGGED_DOT = "ragged-dot"


def _sizes(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "rank": cfg["kv_lora_rank"], "v": cfg["v_head_dim"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"], "e": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"]}


def expert_layers(cfg: dict) -> int:
    s = _sizes(cfg)
    return s["layers"] - s["dense"]


def attn_params(cfg: dict) -> int:
    """W_q, W_kva, the up-projections W_kvb and W_o of one layer."""
    s = _sizes(cfg)
    return (s["d"] * s["h"] * (s["nope"] + s["rope"])
            + s["d"] * (s["rank"] + s["rope"])
            + s["rank"] * s["h"] * (s["nope"] + s["v"])
            + s["h"] * s["v"] * s["d"])


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    s = _sizes(cfg)
    return 3 * s["d"] * s["fe"]


def shared_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return 3 * s["d"] * s["shared"] * s["fe"]


def dense_mlp_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return 3 * s["d"] * s["f"]


def head_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return s["d"] * s["vocab"]


def kv_bytes_per_token(cfg: dict) -> int:
    """The latent and the rotary key of one token over every layer."""
    s = _sizes(cfg)
    return s["layers"] * (s["rank"] + s["rope"]) * BF16


def fixed_bytes(cfg: dict) -> int:
    """What a step reads whatever it routes: attention of every layer, the
    dense layers' MLPs, each expert layer's shared experts and router
    (float32, with its bias), the head."""
    s = _sizes(cfg)
    router = expert_layers(cfg) * (s["d"] + 1) * s["e"] * F32
    return (BF16 * (s["layers"] * attn_params(cfg)
                    + s["dense"] * dense_mlp_params(cfg)
                    + expert_layers(cfg) * shared_params(cfg)
                    + head_params(cfg)) + router)


def expert_bytes(cfg: dict, steps: int, touched: int) -> float:
    """The routed experts touched (summed over steps and expert layers)
    once each, and every step's shared experts: what the ``moe_experts``
    scope reads of weights."""
    return BF16 * (touched * expert_params(cfg)
                   + steps * expert_layers(cfg) * shared_params(cfg))


def latent_bytes(cfg: dict, attended: int) -> float:
    return kv_bytes_per_token(cfg) * attended


def decode_bytes(cfg: dict, steps: int, touched: int, attended: int) -> float:
    return (steps * fixed_bytes(cfg) + BF16 * touched * expert_params(cfg)
            + latent_bytes(cfg, attended))


def decode_flops(cfg: dict, rows: int, attended: int) -> float:
    """Forward FLOPs of ``rows`` row-steps: 2 per matmul parameter a row
    uses (every layer's attention, the dense layers' MLPs, the router, the
    shared experts and ``num_experts_per_tok`` routed experts of every
    expert layer, the head), and the absorbed attention over each attended
    token in every layer: 2 H (rank + rope) for the scores and 2 H rank for
    the values."""
    s = _sizes(cfg)
    per_row = (s["layers"] * attn_params(cfg)
               + s["dense"] * dense_mlp_params(cfg)
               + expert_layers(cfg) * (s["d"] * s["e"] + shared_params(cfg)
                                       + s["k"] * expert_params(cfg))
               + head_params(cfg))
    return (2.0 * rows * per_row
            + 2.0 * s["h"] * (2 * s["rank"] + s["rope"]) * s["layers"]
            * attended)


@functools.cache
def _latent_spans():
    """``harness/spans.py`` loaded once more, as a module of this file's
    own whose ``DEVICE_SCOPES`` also holds the three names of this model
    (as ``loop.py:_loop_spans`` does for the loop's): ``spans.py``
    resolves scope paths against that tuple and may not be edited."""
    spec = importlib.util.find_spec(spans.__name__)
    own = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(own)
    own.DEVICE_SCOPES = own.DEVICE_SCOPES + LATENT_MOE_SCOPES
    return own


def _load(path: str) -> dict | None:
    return _latent_spans().load(path)


def traced(run: dict) -> dict | None:
    """The traced window's counters, or None where the run has no trace,
    its configuration has no latent attention, or its decoder counted
    none of this model's counters (a commit from before them)."""
    marks = run.get("trace_counters")
    if run.get("trace") is None or not marks \
            or not run["config"].get("kv_lora_rank") \
            or "experts_touched" not in marks \
            or not marks["decode_steps"]:
        return None
    return marks


def scope_seconds(run: dict, names: tuple[str, ...], outer: str = "decode",
                  unscoped_ops: tuple[str, ...] = ()) -> float | None:
    """Device seconds under any of the scopes ``names`` inside ``outer``
    in the run's trace, and of the unscoped ops whose instruction name
    holds one of ``unscoped_ops``; None where :func:`traced` is, or where
    the trace holds none of ``names``."""
    if traced(run) is None:
        return None
    path = spans.newest_xplane()
    reduced = _load(path) if path else None
    if reduced is None:
        return None
    seconds = sum(
        s for scopes, s in reduced["scope_s"].items()
        if outer in scopes.split("/")
        and any(name in scopes.split("/") for name in names))
    if not seconds:
        return None
    return seconds + sum(
        s for (scopes, op), s in reduced.get("op_s", {}).items()
        if not scopes and any(name in op for name in unscoped_ops))


def roofline(run: dict, least_bytes: float, seconds: float | None):
    """``least_bytes`` at the chip's HBM bandwidth over ``seconds``, %."""
    from benchmarks.harness.device import peaks

    if not seconds:
        return None
    return 100.0 * least_bytes / (
        peaks(run["device"]["kind"])["hbm_bytes_per_s"] * seconds)
