"""Metric arithmetic the benchmark owns: percentiles where a missing
sample is the worst, spreads, and the operations and bytes a model's work
needs, from the published widths (never from what an implementation
happens to read)."""

from __future__ import annotations

import math
import statistics

MISSING = 1e9  # stands above every finite sample of a time in ms


def percentile(samples: list[float], q: float, n_missing: int = 0) -> float:
    """The ``q``-th percentile (nearest rank) of ``samples`` with
    ``n_missing`` more samples counted above all of them."""
    n = len(samples) + n_missing
    if n == 0:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(q / 100.0 * n))
    if rank > len(samples):
        return MISSING
    return sorted(samples)[rank - 1]


def iqr_share(values: list[float]) -> float:
    """Distance between the first and third quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def layer_params(cfg: dict) -> int:
    """Matrix parameters of one decoder layer (norm gains left out)."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return 2 * d * q + 2 * d * kv + 3 * d * f


def matmul_params(cfg: dict) -> int:
    """Parameters every token multiplies: the layers and the head, not the
    embedding table (a lookup)."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """PaLM/MaxText accounting, forward and backward: 6 per matmul
    parameter, plus causal attention 12·L·H·hd·T halved for causality.
    Recomputation does not count."""
    attn = (12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * seq_len) / 2
    return 6.0 * matmul_params(cfg) + attn


def train_attention_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """Causal attention alone for one step, forward and backward: QK^T and
    PV are 2·T²·hd each per head, halved by causality, and the backward
    costs twice the forward."""
    fwd = (4 * seq_len * seq_len * cfg["head_dim"] / 2
           * cfg["num_attention_heads"] * cfg["num_hidden_layers"] * batch)
    return 3.0 * fwd


def forward_flops(cfg: dict, n_tokens: int, context_sum: int) -> float:
    """Forward pass over ``n_tokens`` tokens whose attention contexts sum
    to ``context_sum`` positions: 2 per matmul parameter per token, and
    4·H·hd per (token, attended position) per layer."""
    attn = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"] * context_sum)
    return 2.0 * matmul_params(cfg) * n_tokens + attn


def decode_step_bytes(cfg: dict, live_context: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode step has to stream: the layer and head matrices
    once at the compute type (bf16), and K and V of ``live_context``
    positions (summed over the live rows) at the cache's type (bf16).
    Nothing else."""
    kv = (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
          * cfg["head_dim"] * 2 * live_context)
    return weight_bytes * matmul_params(cfg) + kv


def module_time(reduced: dict, pattern: str) -> tuple[float, int]:
    """(device seconds, executions) of the XLA modules whose name matches
    ``pattern`` in a reduced trace."""
    import re

    names = [m for m in reduced["module_s"] if re.search(pattern, m)]
    return (sum(reduced["module_s"][m] for m in names),
            sum(reduced["module_n"][m] for m in names))


def op_time(reduced: dict, pattern: str) -> float:
    """Device seconds of the ops whose own name (what stands before
    `` = `` in the HLO line the trace gives) matches ``pattern``."""
    import re

    return sum(s for name, s in reduced["op_s"].items()
               if re.search(pattern, name.split(" = ")[0]))
