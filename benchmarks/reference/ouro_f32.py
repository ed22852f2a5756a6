"""Plain reference of the Ouro looped decoder as ISSUE 36 writes it down:
one stack of sandwich-norm blocks (rotary attention, SwiGLU) run
``total_ut_steps`` times a token with the SAME weights, the final norm
closing every pass and feeding the next, an exit gate on each pass's normed
hidden, an untied head on the last. Straight jax.numpy in float32 at
"highest" matmul precision; no cache, no batching tricks: every pass is a
causal forward over the whole sequence, so "a K/V cache of its own for every
pass" is simply that pass u's queries see pass u's keys.

With x [T, d] the residual stream, N_* RMSNorms with gains (eps
``rms_norm_eps``), rotary theta ``rope_theta`` on q and k at the same
positions in every pass, no bias, no q/k norm, no window:

    layer l, pass u:  a = Attn_l(N_a(x));   x = x + N_a'(a)
                      m = W_down(silu(W_gate N_m(x)) * (W_up N_m(x)))
                      x = x + N_m'(m)
    pass u:           x = layer_{L-1}(... layer_0(x));  h_u = N_final(x)
                      g_u = w_gate . h_u + b_gate;      x = h_u
    token:            x = Embed[token]; u = 0..U-1;  logits = W_head h_{U-1}
    exit:             lam_u = sigmoid(g_u); p_u = lam_u prod_{v<u}(1-lam_v)
                      for u < U-1, p_{U-1} the remainder; exit at the first
                      u whose cumulative p reaches early_exit_threshold

At the published threshold 1.0 the exit is always the last pass;
:func:`logits_at` evaluates the distribution at every judged position and
asserts it. Departures from the release: none intended. What the catalog's
``config`` does not say (the four norms and where they sit, the final norm
between passes, the gate's shape) is under ``assumed`` in the
configuration's file.

It imports nothing of the program. Weights are made and dropped one layer
at a time (each pass makes them again), so the 10.7 GB of float32 never sit
on the chip at once. ``mode`` is the matmul precision as in decoder_f32:
"f32" the reference, "bf16" what the configuration states, "int8" the
control. ``fault`` plants what a wrong loop would do:

- "three_passes": the stack runs U - 1 times.
- "no_loop_norm": the final norm between passes is left out (the one
  before the head stays).
- "shared_cache": ONE K/V cache a layer, written by every pass. A prompt
  is prefilled a pass at a time, so there each pass still reads its own
  keys; a DECODED position t (at or past the row's ``fault_at``) in pass u
  reads, for every position before t, what the LAST pass left there, and
  its own K/V at t. What the last pass left is taken from a first sweep of
  the sound model (the faulty model's own would need one sweep a token);
  the second sweep reads it.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.decoder_f32 import _einsum, _leaf, mm, rms_norm

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "gate", "up", "down", "ln_attn",
                "ln_attn_post", "ln_mlp", "ln_mlp_post")
OUTER_LEAVES = ("embed", "final_norm", "head", "exit_w", "exit_b")
FAULTS = (None, "three_passes", "shared_cache", "no_loop_norm")


@dataclass(frozen=True)
class Widths:
    """The sizes of a configuration file, under its published keys."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    rms_norm_eps: float
    total_ut_steps: int
    early_exit_threshold: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Widths":
        return cls(**{k: cfg[k] for k in cls.__dataclass_fields__})

    def layer_shapes(self) -> dict[str, tuple[int, ...]]:
        d, f = self.hidden_size, self.intermediate_size
        q = self.num_attention_heads * self.head_dim
        kv = self.num_key_value_heads * self.head_dim
        shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                  "gate": (d, f), "up": (d, f), "down": (f, d)}
        return {n: shapes.get(n, (d,)) for n in LAYER_LEAVES}

    def outer_shapes(self) -> dict[str, tuple[int, ...]]:
        d, v = self.hidden_size, self.vocab_size
        return {"embed": (v, d), "final_norm": (d,), "head": (d, v),
                "exit_w": (d, 1), "exit_b": (1,)}


# ---------------------------------------------------------------------------
# Weights from the seed: a matrix N(0, 1/fan_in), a gain 1 + 0.1 N(0, 1),
# as the Mistral configurations draw them; the gate's bias 0.1 N(0, 1)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("w",))
def _layer_weights(base_key, layer, w: Widths):
    """Every leaf of a layer; ``layer`` is traced, so this compiles once."""
    key = jax.random.fold_in(base_key, layer + 1)
    return {n: _leaf(jax.random.fold_in(key, i), n, w.layer_shapes()[n])
            for i, n in enumerate(LAYER_LEAVES)}


@functools.partial(jax.jit, static_argnames=("w", "name"))
def _outer_leaf(base_key, w: Widths, name: str):
    key = jax.random.fold_in(jax.random.fold_in(base_key, 0),
                             OUTER_LEAVES.index(name))
    leaf = _leaf(key, name, w.outer_shapes()[name])
    return leaf - 1.0 if name == "exit_b" else leaf


@functools.partial(jax.jit, static_argnames=("w", "name"))
def _layer_leaf(base_key, layer, w: Widths, name: str):
    key = jax.random.fold_in(jax.random.fold_in(base_key, layer + 1),
                             LAYER_LEAVES.index(name))
    return _leaf(key, name, w.layer_shapes()[name])


def layer_weights(seed: int, w: Widths, layer: int) -> dict:
    return _layer_weights(jax.random.PRNGKey(seed), layer, w)


def layer_leaf(seed: int, w: Widths, layer: int, name: str):
    """One leaf of :func:`layer_weights`, alone (what fills a program
    that stacks each leaf over the layers)."""
    return _layer_leaf(jax.random.PRNGKey(seed), layer, w, name)


def outer_leaf(seed: int, w: Widths, name: str):
    return _outer_leaf(jax.random.PRNGKey(seed), w, name)


# ---------------------------------------------------------------------------
# Forward, one row [T, d] at a time
# ---------------------------------------------------------------------------


def _rope(x, theta: float):
    """x [T, H, hd] at positions 0..T-1: rotate the pairs (x[..., i],
    x[..., i + hd/2])."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def block(x, lw: dict, w: Widths, mode: str = "f32", left=None,
          fault_at=None):
    """One layer on one row x [T, d], causal over T. Returns (x, k, v).
    ``left`` = (k, v) [T, Hkv, hd] plants the shared cache: a query at or
    past ``fault_at`` reads ``left`` at every position before its own."""
    t, _ = x.shape
    hq, hkv, hd = w.num_attention_heads, w.num_key_value_heads, w.head_dim
    h = rms_norm(x, lw["ln_attn"], w.rms_norm_eps)
    q = _rope(mm(h, lw["wq"], mode).reshape(t, hq, hd), w.rope_theta)
    k = _rope(mm(h, lw["wk"], mode).reshape(t, hkv, hd), w.rope_theta)
    v = mm(h, lw["wv"], mode).reshape(t, hkv, hd)
    qg = q.reshape(t, hkv, hq // hkv, hd)
    at, before = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    scores = _einsum("skgd,tkd->kgst", qg, k, mode)
    if left is not None:
        stale = (at >= fault_at) & (before < at)
        scores = jnp.where(stale, _einsum("skgd,tkd->kgst", qg, left[0],
                                          mode), scores)
    probs = jax.nn.softmax(
        jnp.where(at >= before, scores * hd ** -0.5, -1e30), axis=-1)
    if left is None:
        ctx = _einsum("kgst,tkd->skgd", probs, v, mode)
    else:
        ctx = (_einsum("kgst,tkd->skgd", jnp.where(stale, 0.0, probs), v,
                       mode)
               + _einsum("kgst,tkd->skgd", jnp.where(stale, probs, 0.0),
                         left[1], mode))
    a = mm(ctx.reshape(t, hq * hd), lw["wo"], mode)
    x = x + rms_norm(a, lw["ln_attn_post"], w.rms_norm_eps)
    h = rms_norm(x, lw["ln_mlp"], w.rms_norm_eps)
    m = mm(jax.nn.silu(mm(h, lw["gate"], mode)) * mm(h, lw["up"], mode),
           lw["down"], mode)
    return x + rms_norm(m, lw["ln_mlp_post"], w.rms_norm_eps), k, v


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _block_rows(x, lw, w: Widths, mode: str, left, fault_at):
    """:func:`block` one row of the batch at a time (bounded memory)."""
    if left is None:
        return jax.lax.map(lambda row: block(row, lw, w, mode), x)
    return jax.lax.map(lambda r: block(r[0], lw, w, mode, r[1], r[2]),
                       (x, left, fault_at))


@functools.partial(jax.jit, static_argnames=("w",))
def _close_pass(x, final_norm, positions, w: Widths):
    """The final norm on x [B, T, d], and its rows at ``positions``."""
    h = rms_norm(x, final_norm, w.rms_norm_eps)
    return h, jnp.take_along_axis(h, positions[:, :, None], axis=1)


def _passes(seed: int, w: Widths, tokens, positions, mode: str, n_passes: int,
            loop_norm: bool = True, left=None, fault_at=None, keep=False):
    """``n_passes`` passes over tokens [B, T]. Returns (the normed hidden
    of every pass at ``positions`` [B, P]: [U, B, P, d], and with ``keep``
    the last pass's (k, v) of every layer)."""
    x = outer_leaf(seed, w, "embed")[jnp.asarray(tokens)]
    final_norm = outer_leaf(seed, w, "final_norm")
    picked, kept = [], []
    for u in range(n_passes):
        for i in range(w.num_hidden_layers):
            x, k, v = _block_rows(x, layer_weights(seed, w, i), w, mode,
                                  left[i] if left is not None
                                  and u < n_passes - 1 else None, fault_at)
            if keep and u == n_passes - 1:
                kept.append((k, v))
        h, at = _close_pass(x, final_norm, positions, w)
        picked.append(at)
        if loop_norm:
            x = h
    return jnp.stack(picked), kept


def exit_pass(hidden, exit_w, exit_b, threshold: float):
    """The pass each position exits at, from its normed hidden of every
    pass [U, ..., d]: the first whose cumulative exit probability reaches
    ``threshold``; the last pass takes what is left, so its cumulative
    probability is 1."""
    lam = jax.nn.sigmoid(jnp.matmul(
        hidden, exit_w, precision=jax.lax.Precision.HIGHEST)[..., 0] + exit_b)
    cum = 1.0 - jnp.cumprod(1.0 - lam, axis=0)
    cum = cum.at[-1].set(1.0)
    return jnp.argmax(cum >= threshold, axis=0)


def logits_at(seed: int, w: Widths, tokens: np.ndarray,
              positions: np.ndarray, mode: str = "f32",
              fault: str | None = None, fault_at: np.ndarray | None = None):
    """Logits [B, P, V] at ``positions`` [B, P] of ``tokens`` [B, T]: the
    head on the last pass's normed hidden. ``fault_at`` [B] is where each
    row's decoded positions begin (its prompt's length), for
    "shared_cache"."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    positions = jnp.asarray(positions)
    n = w.total_ut_steps - (fault == "three_passes")
    left = None
    if fault == "shared_cache":
        _, left = _passes(seed, w, tokens, positions, mode, n, keep=True)
        fault_at = jnp.asarray(fault_at)
    hidden, _ = _passes(seed, w, tokens, positions, mode, n,
                        loop_norm=fault != "no_loop_norm", left=left,
                        fault_at=fault_at)
    if fault is None:
        exits = exit_pass(hidden, outer_leaf(seed, w, "exit_w"),
                          outer_leaf(seed, w, "exit_b"),
                          w.early_exit_threshold)
        if w.early_exit_threshold >= 1.0 and not bool(
                jnp.all(exits == n - 1)):
            raise AssertionError(
                "a position exits before the last pass at threshold "
                f"{w.early_exit_threshold}")
    return mm(hidden[-1], outer_leaf(seed, w, "head"), mode)
