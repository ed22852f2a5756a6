"""Counts and trace readings for configurations with ``mixer_types``
(lightning linear-attention layers and block-sparse attention layers in
one decoder): the operations and bytes a decode step needs, from the
published widths and from what the decoder counted, and the device time
under the mixers' own scopes.

Least bytes of a decode step: every layer and head matrix once at bf16; per
live row and lightning layer its float32 state read and written once; per
live row and sparse layer K and V of the tokens the selection attends (the
whole context at or under ``dense_len``) and, over it, the compressed keys
the selection scores. Nothing else: no gathered copy, no block read and
masked.
"""

from __future__ import annotations

import functools
import importlib.util

from benchmarks.harness import spans

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# The program's names (kubeflow_tpu/observability/tracing.py:MIXER_SCOPES),
# quoted and not imported, as spans.DEVICE_SCOPES quotes the others.
MIXER_SCOPES = ("linear_attn", "sparse_select", "sparse_attn")
# One step a dispatch, or ``decode_chunk`` of them fused: the counters
# count steps either way.
DECODE_MODULE = r"^jit_decode_(step|chunk)$"


def _sizes(cfg: dict) -> dict:
    kinds = cfg["mixer_types"]
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "hd": cfg["head_dim"], "h": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "v": cfg["vocab_size"],
            "n_lightning": kinds.count(LIGHTNING),
            "n_sparse": kinds.count(SPARSE), **cfg["sparse_config"]}


def matmul_params(cfg: dict) -> int:
    """Parameters every token multiplies: the layers' matrices (q, k, v,
    o and the gate of each mixer, the SwiGLU) and the head."""
    s = _sizes(cfg)
    q = s["h"] * s["hd"]
    ffn = 3 * s["d"] * s["f"]
    lightning = 5 * s["d"] * q + ffn
    sparse = 3 * s["d"] * q + 2 * s["d"] * s["hkv"] * s["hd"] + ffn
    return (s["n_lightning"] * lightning + s["n_sparse"] * sparse
            + s["d"] * s["v"])


def row_steps(counters: dict, cfg: dict) -> dict:
    """What the decoder counted over some decode steps, as the counts
    below want it: ``rows`` (row-steps), ``attended`` (tokens the sparse
    layers' selection read, a layer) and ``windows`` (compressed keys it
    scored, a layer: the sparse rows' contexts over the stride). A sparse
    row attends ``topk - 1`` whole blocks and half a block on average,
    which splits the counted contexts between the two kinds of row."""
    s = _sizes(cfg)
    sparse_attended = counters["rows_sparse"] * (
        (s["topk"] - 1) * s["block_size"] + (s["block_size"] + 1) / 2)
    dense_context = max(0.0, counters["sparse_tokens_attended"]
                        - sparse_attended)
    sparse_context = max(0.0, counters["sparse_tokens_in_context"]
                         - dense_context)
    return {"rows": counters["rows_dense"] + counters["rows_sparse"],
            "attended": counters["sparse_tokens_attended"],
            "windows": sparse_context / s["kernel_stride"]}


def linear_attn_bytes(cfg: dict, n: dict) -> float:
    s = _sizes(cfg)
    return s["n_lightning"] * n["rows"] * 2 * 4 * s["h"] * s["hd"] ** 2


def sparse_attn_bytes(cfg: dict, n: dict) -> float:
    """K and V of the attended tokens and the scored compressed keys, at
    bf16, over the sparse layers."""
    s = _sizes(cfg)
    per_token = 2 * s["hkv"] * s["hd"]
    return s["n_sparse"] * (2 * per_token * n["attended"]
                            + per_token * n["windows"])


def decode_bytes(cfg: dict, steps: int, n: dict) -> float:
    return (2.0 * matmul_params(cfg) * steps + linear_attn_bytes(cfg, n)
            + sparse_attn_bytes(cfg, n))


def decode_flops(cfg: dict, n: dict) -> float:
    """Forward FLOPs of the counted row-steps: 2 per matmul parameter; the
    lightning state's decay, update and read (5 per element); 4·H·hd per
    attended token and 2·H·hd per scored compressed key."""
    s = _sizes(cfg)
    heads = s["h"] * s["hd"]
    return (2.0 * matmul_params(cfg) * n["rows"]
            + s["n_lightning"] * n["rows"] * 5 * heads * s["hd"]
            + s["n_sparse"] * (4 * heads * n["attended"]
                               + 2 * heads * n["windows"]))


@functools.cache
def _mixer_spans():
    """``harness/spans.py`` loaded a second time, as a module of this
    file's own whose ``DEVICE_SCOPES`` also holds the mixers' names.
    ``spans.py`` resolves scope paths against that tuple and may not be
    edited; the instance every other reader imports is left as it is."""
    spec = importlib.util.find_spec(spans.__name__)
    own = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(own)
    own.DEVICE_SCOPES = own.DEVICE_SCOPES + MIXER_SCOPES
    return own


def _load(path: str) -> dict | None:
    """``spans.load`` with the mixers' scopes among the names it knows."""
    return _mixer_spans().load(path)


def scope_seconds(run: dict, names: tuple[str, ...],
                  outer: str = "decode") -> float | None:
    """Device seconds under any of the scopes ``names`` inside ``outer``
    in the run's trace; None without a trace, or where the program set
    none of ``names`` (a commit from before they existed)."""
    if run.get("trace") is None or not run["config"].get("mixer_types"):
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


def traced(run: dict) -> tuple[dict, dict] | None:
    """(the traced window's counters, their row-step counts), or None
    where the run has no trace or its decoder counted nothing."""
    marks = run.get("trace_counters")
    if run.get("trace") is None or not marks \
            or "rows_sparse" not in marks or not marks["decode_steps"]:
        return None
    return marks, row_steps(marks, run["config"])
