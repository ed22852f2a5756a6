"""Plain reference of Kanana-2-30B-A3B (``model_type`` deepseek_v3):
latent attention with no query compression, a
dense first layer, then layers of 128 sigmoid-routed experts beside a shared
expert, an untied head. Straight jax.numpy in float32 at "highest" matmul
precision; no cache, no batching tricks: attention is the EXPANDED form
(every head's key and value made from the latent), and every token's
chosen experts are computed exactly, with no capacity.

With x [T, d] the residual stream, N_* RMSNorms with gains (eps
``rms_norm_eps``), H heads, p the position:

    layer 0 (first_k_dense_replace):  x = x + MLA(N_a(x));  x = x + MLP(N_m(x))
    layers 1.. (moe_layer_freq 1):    x = x + MLA(N_a(x));  x = x + MoE(N_m(x))
    token:                            x = Embed[id];  logits = W_head N_f(x)

    MLA(h):  q = h W_q (H x [nope | rope]);  q_rope = RoPE(q_rope, p)
             [c | k_r] = h W_kva;  c = N_kv(c);  k_r = RoPE(k_r, p) (one for all heads)
             [k_nope_i | v_i] = c W_kvb,i
             s_i(t, j) = (q_nope_i(t) . k_nope_i(j) + q_rope_i(t) . k_r(j))
                         / sqrt(nope + rope),  j <= t
             o_i(t) = sum_j softmax_j(s_i) v_i(j);   MLA = [o_1 .. o_H] W_o
    RoPE:    theta ``rope_theta`` over the rope dims, pairs (2k, 2k+1)
             (rope_interleave true); no rope_scaling, so no mscale
    MoE(u):  g = sigmoid(u W_r);  S = top-k experts by g + b;
             w_e = routed_scaling_factor * g_e / sum_{f in S} g_f  (b selects, never weighs)
             MoE = sum_{e in S} w_e E_e(u) + E_sh(u);  E(u) = W_down(silu(W_gate u) * W_up u)
             the shared expert one MLP n_shared_experts x moe_intermediate_size wide

Departures from the release: none in the mathematics. What the catalog's
``config`` does not say (the dtype, the selection bias, the weights) is
under ``assumed`` in the configuration's file. The program holds the rope
columns of W_q and W_kva de-interleaved (:func:`rope_columns`), so that its
half-split rotary computes this function: a layout, not a change of it.

It imports nothing of the program. Weights are drawn from ``--seed`` leaf by
leaf and each routed expert from a key folded from (seed, layer, expert),
and used one layer (one expert) at a time, so the 15 GB of float32 never sit
on the chip at once. ``mode`` is the matmul precision as in decoder_f32:
"f32" the reference, "bf16" what the configuration states, "int8" the
control. ``fault`` plants what a wrong block would do:

- "softmax_routing": softmax over the router's logits in the sigmoid's place.
- "no_routed_scaling": the routed weights not multiplied by the factor.
- "no_shared": the shared expert left out.
- "capacity": tokens dropped past a capacity factor of 1.25 a row, as the
  GShard layer drops them (``max(int(n k / E x 1.25), k)`` a expert over a
  row's n real tokens, all first choices placed before any second).
- "no_kv_norm": the latent's norm N_kv left out.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.decoder_f32 import _einsum, _leaf, mm, rms_norm

ATTN_LEAVES = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln_attn", "ln_mlp")
DENSE_LEAVES = ("gate", "up", "down")
MOE_LEAVES = ("router", "bias", "shared_gate", "shared_up", "shared_down")
EXPERT_LEAVES = ("gate", "up", "down")
OUTER_LEAVES = ("embed", "final_norm", "head")
FAULTS = (None, "softmax_routing", "no_routed_scaling", "no_shared",
          "capacity", "no_kv_norm")
CAPACITY_FACTOR = 1.25
EXPERT_KEY = 1000  # fold_in(layer key, EXPERT_KEY) then the expert's index
ROWS_BUCKET = 256  # the experts' token lists are padded to a multiple of this
# A choice is a near tie where the last chosen expert's score + bias lies
# within this of the first left out: about what rounding the router's
# input to bfloat16 moves a sigmoid score by.
NEAR_TIE = 1e-3


@dataclass(frozen=True)
class Widths:
    """The sizes of a configuration file, under its published keys."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    routed_scaling_factor: float
    first_k_dense_replace: int
    selection_bias_scale: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Widths":
        return cls(**{k: cfg[k] for k in cls.__dataclass_fields__})

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def layer_shapes(self, layer: int) -> dict[str, tuple[int, ...]]:
        d, h = self.hidden_size, self.num_attention_heads
        nope, rope = self.qk_nope_head_dim, self.qk_rope_head_dim
        rank, v = self.kv_lora_rank, self.v_head_dim
        shapes = {"wq": (d, h * (nope + rope)), "wkv_a": (d, rank + rope),
                  "kv_norm": (rank,), "wkv_b": (rank, h * (nope + v)),
                  "wo": (h * v, d), "ln_attn": (d,), "ln_mlp": (d,)}
        if self.is_moe(layer):
            fs = self.n_shared_experts * self.moe_intermediate_size
            shapes.update(router=(d, self.n_routed_experts),
                          bias=(self.n_routed_experts,),
                          shared_gate=(d, fs), shared_up=(d, fs),
                          shared_down=(fs, d))
        else:
            f = self.intermediate_size
            shapes.update(gate=(d, f), up=(d, f), down=(f, d))
        return shapes

    def expert_shapes(self) -> dict[str, tuple[int, ...]]:
        d, f = self.hidden_size, self.moe_intermediate_size
        return {"gate": (d, f), "up": (d, f), "down": (f, d)}

    def outer_shapes(self) -> dict[str, tuple[int, ...]]:
        d, v = self.hidden_size, self.vocab_size
        return {"embed": (v, d), "final_norm": (d,), "head": (d, v)}


def leaf_names(moe: bool) -> tuple[str, ...]:
    return ATTN_LEAVES + (MOE_LEAVES if moe else DENSE_LEAVES)


# ---------------------------------------------------------------------------
# Weights from the seed: a matrix N(0, 1/fan_in), a gain 1 + 0.1 N(0, 1), as
# the other configurations draw them; the selection bias
# selection_bias_scale x N(0, 1)
# ---------------------------------------------------------------------------


def _draw(key, name: str, shape, w: Widths):
    if name == "bias":
        return w.selection_bias_scale * jax.random.normal(key, shape,
                                                          jnp.float32)
    return _leaf(key, name, shape)


@functools.partial(jax.jit, static_argnames=("w", "moe"))
def _layer_leaves(base_key, layer, w: Widths, moe: bool) -> dict:
    """Every leaf of a layer but its routed experts; ``layer`` is traced,
    so this compiles once for each kind of layer."""
    key = jax.random.fold_in(base_key, layer + 1)
    shapes = w.layer_shapes(w.first_k_dense_replace if moe else 0)
    return {name: _draw(jax.random.fold_in(key, i), name, shapes[name], w)
            for i, name in enumerate(leaf_names(moe))}


@functools.partial(jax.jit, static_argnames=("w", "name"))
def _expert_leaves(base_key, layer, w: Widths, name: str):
    """One matrix of every routed expert of a layer [E, ...], each from
    the key folded from (layer, expert); ``layer`` is traced, so this
    compiles once a matrix."""
    key = _experts_key(base_key, layer)
    return jax.vmap(lambda e: _expert_leaf(key, e, w, name))(
        jnp.arange(w.n_routed_experts))


def _experts_key(base_key, layer):
    return jax.random.fold_in(jax.random.fold_in(base_key, layer + 1),
                              EXPERT_KEY)


def _expert_leaf(layer_key, expert, w: Widths, name: str):
    """One matrix of one routed expert, ``layer_key`` the layer's
    :func:`_experts_key`."""
    key = jax.random.fold_in(jax.random.fold_in(layer_key, expert),
                             EXPERT_LEAVES.index(name))
    return _leaf(key, name, w.expert_shapes()[name])


@functools.partial(jax.jit, static_argnames=("w",))
def _outer_leaves(base_key, w: Widths) -> dict:
    key = jax.random.fold_in(base_key, 0)
    return {name: _leaf(jax.random.fold_in(key, i), name,
                        w.outer_shapes()[name])
            for i, name in enumerate(OUTER_LEAVES)}


def layer_weights(seed: int, w: Widths, layer: int) -> dict:
    """Every leaf of a layer but its routed experts."""
    return _layer_leaves(jax.random.PRNGKey(seed), layer, w, w.is_moe(layer))


def layer_leaf(seed: int, w: Widths, layer: int, name: str):
    return layer_weights(seed, w, layer)[name]


def expert_leaves(seed: int, w: Widths, layer: int, name: str):
    """Matrix ``name`` of every routed expert of ``layer``, [E, ...]."""
    return _expert_leaves(jax.random.PRNGKey(seed), layer, w, name)


def outer_leaf(seed: int, w: Widths, name: str):
    return _outer_leaves(jax.random.PRNGKey(seed), w)[name]


def rope_columns(w: Widths) -> tuple[np.ndarray, np.ndarray]:
    """The column orders under which the program holds W_q ([H x (nope +
    rope)]) and W_kva ([rank + rope]): each rotary part de-interleaved,
    its even dims first and its odd dims after, so that the half-split
    rotary's pair (i, i + rope/2) is the published pair (2i, 2i + 1) at
    the same frequency. Scores are unchanged: q and k move alike."""
    nope, rope = w.qk_nope_head_dim, w.qk_rope_head_dim
    half = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    head = np.concatenate([np.arange(nope), nope + half])
    q = np.concatenate([i * (nope + rope) + head
                        for i in range(w.num_attention_heads)])
    kv = np.concatenate([np.arange(w.kv_lora_rank), w.kv_lora_rank + half])
    return q, kv


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _rope_interleaved(x, theta: float):
    """x [T, ..., r] at positions 0..T-1: rotate the pairs (x[..., 2k],
    x[..., 2k + 1]) by p theta^(-2k/r), left interleaved."""
    t, r = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.outer(jnp.arange(t, dtype=jnp.float32), inv)
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), r // 2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def attention(x, lw: dict, w: Widths, mode: str = "f32",
              fault: str | None = None):
    """x + MLA(N_a(x)) on one row x [T, d], causal over T."""
    t, _ = x.shape
    h, nope, rope = (w.num_attention_heads, w.qk_nope_head_dim,
                     w.qk_rope_head_dim)
    rank, v = w.kv_lora_rank, w.v_head_dim
    a = rms_norm(x, lw["ln_attn"], w.rms_norm_eps)
    q = mm(a, lw["wq"], mode).reshape(t, h, nope + rope)
    q_nope = q[..., :nope]
    q_rope = _rope_interleaved(q[..., nope:], w.rope_theta)
    kv = mm(a, lw["wkv_a"], mode)
    c = kv[:, :rank]
    if fault != "no_kv_norm":
        c = rms_norm(c, lw["kv_norm"], w.rms_norm_eps)
    k_rope = _rope_interleaved(kv[:, rank:], w.rope_theta)
    kvb = mm(c, lw["wkv_b"], mode).reshape(t, h, nope + v)
    k_nope, val = kvb[..., :nope], kvb[..., nope:]
    scores = (_einsum("shn,thn->hst", q_nope, k_nope, mode)
              + _einsum("shr,tr->hst", q_rope, k_rope, mode))
    at, before = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(at >= before,
                                     scores * (nope + rope) ** -0.5, -1e30),
                           axis=-1)
    ctx = _einsum("hst,thv->shv", probs, val, mode).reshape(t, h * v)
    return x + mm(ctx, lw["wo"], mode)


@functools.partial(jax.jit, static_argnames=("w", "mode", "fault"))
def _attention_rows(x, lw, w: Widths, mode: str, fault):
    """:func:`attention` one row of the batch at a time (bounded memory)."""
    return jax.lax.map(lambda row: attention(row, lw, w, mode, fault), x)


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _dense_mlp(x, lw, w: Widths, mode: str):
    u = rms_norm(x, lw["ln_mlp"], w.rms_norm_eps)
    return x + mm(jax.nn.silu(mm(u, lw["gate"], mode)) * mm(u, lw["up"], mode),
                  lw["down"], mode)


@functools.partial(jax.jit, static_argnames=("w", "mode", "fault"))
def _route(x, lw, w: Widths, mode: str, fault):
    """The normed input u [N, d] of an expert layer's x [..., d] (N its
    tokens), each token's chosen experts [N, k] in the order of their
    score and their weights [N, k], the shared expert's output [N, d], and
    by how much the last choice beat the first left out [N]."""
    x = x.reshape(-1, x.shape[-1])
    u = rms_norm(x, lw["ln_mlp"], w.rms_norm_eps)
    logits = mm(u, lw["router"], mode)
    scores = (jax.nn.softmax(logits, axis=-1) if fault == "softmax_routing"
              else jax.nn.sigmoid(logits))
    ranked, chosen = jax.lax.top_k(scores + lw["bias"],
                                   w.num_experts_per_tok + 1)
    margin = ranked[:, -2] - ranked[:, -1]
    chosen = chosen[:, :-1]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = picked / jnp.sum(picked, -1, keepdims=True)
    if fault != "no_routed_scaling":
        weight = weight * w.routed_scaling_factor
    shared = mm(jax.nn.silu(mm(u, lw["shared_gate"], mode))
                * mm(u, lw["shared_up"], mode), lw["shared_down"], mode)
    if fault == "no_shared":
        shared = jnp.zeros_like(shared)
    return u, chosen, weight, shared, margin


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _experts_add(x, shared, base_key, layer, u, rows, weight, w: Widths,
                 mode: str):
    """x [B, T, d] + shared [B*T, d] + every routed expert of ``layer``
    over its tokens, one expert at a time: ``rows`` [E, C] the token
    indices into u that chose each expert (padded with an index past the
    end), ``weight`` [E, C] their weights. C is the most tokens any expert
    took, so none is dropped."""
    key = _experts_key(base_key, layer)

    def one(acc, expert):
        def leaf(name):
            return _expert_leaf(key, expert, w, name)

        r = rows[expert]
        xs = u[jnp.minimum(r, u.shape[0] - 1)]
        y = mm(jax.nn.silu(mm(xs, leaf("gate"), mode))
               * mm(xs, leaf("up"), mode), leaf("down"), mode)
        return acc.at[r].add(y * weight[expert][:, None], mode="drop"), None

    acc = jax.lax.scan(one, x.reshape(shared.shape) + shared,
                       jnp.arange(w.n_routed_experts))[0]
    return acc.reshape(x.shape)


def _kept(chosen: np.ndarray, real: np.ndarray, w: Widths) -> np.ndarray:
    """[B, T, k] True where a choice survives the "capacity" fault: per
    row of ``real`` tokens, an expert takes at most max(int(n k / E x
    1.25), k) of them, first choices before second ones, tokens in
    order; pad positions claim nothing."""
    b, t, k = chosen.shape
    e = w.n_routed_experts
    kept = np.zeros_like(chosen, dtype=bool)
    for i in range(b):
        n = int(real[i])
        cap = max(int(n * k / e * CAPACITY_FACTOR), k)
        load = np.zeros(e, np.int64)
        for slot in range(k):
            for tok in range(n):
                ex = chosen[i, tok, slot]
                if load[ex] < cap:
                    load[ex] += 1
                    kept[i, tok, slot] = True
    return kept


def _moe(x, lw: dict, seed: int, w: Widths, layer: int, mode: str, fault,
         real: np.ndarray, ties: list | None):
    """x + MoE(N_m(x)) for x [B, T, d] (``lw`` the layer's other leaves):
    every (token, choice) pair computed, an expert at a time over the
    tokens that chose it. ``ties`` gets (near ties, real tokens)."""
    b, t, d = x.shape
    u, chosen, weight, shared, margin = _route(x, lw, w, mode, fault)
    if ties is not None:
        is_real = (np.arange(t)[None] < real[:, None]).reshape(-1)
        near = np.asarray(margin) < NEAR_TIE
        ties.append((int((near & is_real).sum()), int(is_real.sum())))
    chosen_np = np.asarray(chosen)
    weight_np = np.asarray(weight)
    kept = (_kept(chosen_np.reshape(b, t, -1), real, w).reshape(b * t, -1)
            if fault == "capacity" else np.ones(chosen_np.shape, bool))
    # Each expert's (token, weight) list, experts in order, padded to the
    # longest list rounded up to whole buckets.
    tok, slot = np.nonzero(kept)
    expert = chosen_np[tok, slot]
    counts = np.bincount(expert, minlength=w.n_routed_experts)
    width = -(-max(int(counts.max()), 1) // ROWS_BUCKET) * ROWS_BUCKET
    rows = np.full((w.n_routed_experts, width), b * t, np.int32)
    wts = np.zeros((w.n_routed_experts, width), np.float32)
    order = np.argsort(expert, kind="stable")
    at = np.arange(order.size) - np.repeat(np.cumsum(counts) - counts,
                                           counts)
    rows[expert[order], at] = tok[order]
    wts[expert[order], at] = weight_np[tok[order], slot[order]]
    return _experts_add(x, shared, jax.random.PRNGKey(seed), layer, u, rows,
                        wts, w, mode)


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _head(x, final_norm, head, positions, w: Widths, mode: str):
    h = jnp.take_along_axis(rms_norm(x, final_norm, w.rms_norm_eps),
                            positions[:, :, None], axis=1)
    return mm(h, head, mode)


def logits_at(seed: int, w: Widths, tokens: np.ndarray,
              positions: np.ndarray, mode: str = "f32",
              fault: str | None = None, lengths: np.ndarray | None = None,
              ties: list | None = None):
    """Logits [B, P, V] at ``positions`` [B, P] of ``tokens`` [B, T]; a
    row's tokens past ``lengths[b]`` (default: all T real) are padding,
    which only the "capacity" fault and the count of near ties need to
    know. ``ties``, where given, gets one (near ties, real tokens) an
    expert layer: the tokens whose last choice beat the first left out by
    under :data:`NEAR_TIE`."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    tokens = np.asarray(tokens)
    real = (np.full(tokens.shape[0], tokens.shape[1]) if lengths is None
            else np.asarray(lengths))
    x = outer_leaf(seed, w, "embed")[jnp.asarray(tokens)]
    for layer in range(w.num_hidden_layers):
        lw = layer_weights(seed, w, layer)
        x = _attention_rows(x, {n: lw[n] for n in ATTN_LEAVES}, w, mode,
                            fault)
        if w.is_moe(layer):
            x = _moe(x, lw, seed, w, layer, mode, fault, real, ties)
        else:
            x = _dense_mlp(x, lw, w, mode)
    return _head(x, outer_leaf(seed, w, "final_norm"),
                 outer_leaf(seed, w, "head"), jnp.asarray(positions), w,
                 mode)
