"""Counts and trace readings for looped configurations (``total_ut_steps``
passes of one stack of layers a token, a K/V cache of its own for every
pass): the operations and bytes a decode step needs, from the published
widths and from what the decoder counted, and the device time under the
scopes a looped stack sets.

Least bytes of a decode step: the layers' matrices once A PASS at bf16
(nothing keeps a stack of gigabytes on the chip between passes), the head
once, and K and V of the tokens every cache layer attends
(``kv_tokens_attended``: per emitted token, its row's length). Nothing
else: no row read past its live tokens, no embedding row, no norm gain.
"""

from __future__ import annotations

import functools
import importlib.util

from benchmarks.harness import spans

# The program's names (kubeflow_tpu/observability/tracing.py:LOOP_SCOPES),
# quoted and not imported, as spans.DEVICE_SCOPES quotes the others.
LOOP_SCOPES = ("loop_norm", "post_norm")
DECODE_MODULE = r"^jit_decode_(step|chunk)$"
BF16 = 2


def _sizes(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "hd": cfg["head_dim"], "h": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"],
            "passes": cfg["total_ut_steps"]}


def attn_params(cfg: dict) -> int:
    """q, k, v and o of every layer of the stack, once."""
    s = _sizes(cfg)
    return s["layers"] * 2 * s["d"] * s["hd"] * (s["h"] + s["hkv"])


def mlp_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return s["layers"] * 3 * s["d"] * s["f"]


def head_params(cfg: dict) -> int:
    s = _sizes(cfg)
    return s["d"] * s["v"]


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over every cache layer (pass x layer)."""
    s = _sizes(cfg)
    return 2 * s["passes"] * s["layers"] * s["hkv"] * s["hd"] * BF16


def attn_bytes(cfg: dict, steps: int, attended: int) -> float:
    """The attention matrices once a pass, and K/V of the attended
    tokens (``attended``: the sum over emitted tokens of their rows'
    lengths) over every cache layer."""
    return (BF16 * attn_params(cfg) * _sizes(cfg)["passes"] * steps
            + kv_bytes_per_token(cfg) * attended)


def mlp_bytes(cfg: dict, steps: int) -> float:
    return BF16 * mlp_params(cfg) * _sizes(cfg)["passes"] * steps


def decode_bytes(cfg: dict, steps: int, attended: int) -> float:
    return (attn_bytes(cfg, steps, attended) + mlp_bytes(cfg, steps)
            + BF16 * head_params(cfg) * steps)


def decode_flops(cfg: dict, rows: int, attended: int) -> float:
    """Forward FLOPs of ``rows`` row-steps: 2 per matmul parameter, the
    layers' counted every pass and the head once; 4·H·hd per attended
    token and cache layer (scores and values)."""
    s = _sizes(cfg)
    return (2.0 * rows * (s["passes"] * (attn_params(cfg) + mlp_params(cfg))
                          + head_params(cfg))
            + 4.0 * s["h"] * s["hd"] * s["passes"] * s["layers"] * attended)


@functools.cache
def _loop_spans():
    """``harness/spans.py`` loaded once more, as a module of this file's
    own whose ``DEVICE_SCOPES`` also holds the loop's two names (as
    ``hybrid.py:_mixer_spans`` does for the mixers'): ``spans.py``
    resolves scope paths against that tuple and may not be edited."""
    spec = importlib.util.find_spec(spans.__name__)
    own = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(own)
    own.DEVICE_SCOPES = own.DEVICE_SCOPES + LOOP_SCOPES
    return own


def _load(path: str) -> dict | None:
    return _loop_spans().load(path)


def traced(run: dict) -> dict | None:
    """The traced window's counters, or None where the run has no trace,
    its configuration does not loop, or its decoder counted none of the
    loop's counters (a commit from before them)."""
    marks = run.get("trace_counters")
    if run.get("trace") is None or not marks \
            or not run["config"].get("total_ut_steps") \
            or "kv_tokens_attended" not in marks \
            or not marks["decode_steps"]:
        return None
    return marks


def scope_seconds(run: dict, names: tuple[str, ...],
                  outer: str = "decode") -> float | None:
    """Device seconds under any of the scopes ``names`` inside ``outer``
    in the run's trace; None where :func:`traced` is, or where the trace
    holds none of ``names``."""
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
    return seconds or None


def roofline(run: dict, least_bytes: float, seconds: float | None):
    """``least_bytes`` at the chip's HBM bandwidth over ``seconds``, %."""
    from benchmarks.harness.device import peaks

    if not seconds:
        return None
    return 100.0 * least_bytes / (
        peaks(run["device"]["kind"])["hbm_bytes_per_s"] * seconds)
