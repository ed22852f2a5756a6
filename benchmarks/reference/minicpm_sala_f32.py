"""Plain reference of the MiniCPM-SALA decoder as ISSUE 28 writes it down:
lightning (decayed linear attention) layers and InfLLM-v2 block-sparse
attention layers in one pre-norm decoder with muP scalings, SwiGLU and an
untied head. Straight jax.numpy in float32 at "highest" matmul precision;
no cache, no kernel: the lightning layer is the token recurrence itself,
the sparse layer scores every key of the sequence under a mask. Work goes
one row, one block of queries and one block of tokens at a time only so
that the real sizes fit a 16 GB chip.

With L the published depth (it does not follow a depth cut), d the hidden
size and s = scale_depth / sqrt(L):

    x0 = embed[tokens] * scale_emb
    h  = x + s * Mixer(RMSNorm(x));   y = h + s * MLP(RMSNorm(h))
    logits = head(RMSNorm(x_last) / (d / dim_model_base))

lightning-attn, per head: q = RoPE(RMSNorm_hd(Wq u)), k likewise,
v = Wv u; S_t = lam_h S_{t-1} + k_t^T v_t; o_t = (q_t / sqrt(hd)) S_t;
lam_h = exp(-2^(-8h/H)), h = 1..H; out = Wo(RMSNorm_d(concat o) *
sigmoid(Wg u)).

minicpm4, no rotary: q = RMSNorm_hd(Wq u), k = RMSNorm_hd(Wk u), v = Wv u.
A query at position t with n = t + 1 tokens of context reads all of them
if n <= dense_len. Otherwise: compressed keys Kc_j = mean(K[stride*j :
stride*j + kernel]) for the windows that end at or before t; per query
head p_j = softmax_j(q.Kc_j / sqrt(hd)); g_j = sum of p_j over the KV
head's query heads; a block scores the max of g_j over the windows that
overlap it; read = the first init_blocks blocks, the blocks holding the
last window_size tokens, and the best others up to topk blocks in all
(ties to the lower index); softmax attention over the read blocks' tokens
up to t. out = Wo(o * sigmoid(Wg u)).

It imports nothing of the program. ``mode`` is the matmul precision, as in
decoder_f32: "f32" the reference, "bf16" what the configuration states,
"int8" the control. ``fault`` plants what a wrong cache would do:
"no_selection" reads the forced blocks only, "state_dropped" restarts
every lightning state at the row's ``fault_at`` position (the prompt's
end, as an admission that lost the prefill's state would).
"selection_bf16" is no fault but a gauge: everything in float32 but the
block scores, which see q and the compressed keys rounded to bfloat16 as
the program's do, so that what flipped marginal blocks cost can be read
(:func:`selection_flips` counts them).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.decoder_f32 import _einsum, _leaf, mm, rms_norm

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
MIXER_LEAVES = ("wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm")
LAYER_LEAVES = {
    LIGHTNING: MIXER_LEAVES + ("o_norm", "gate", "up", "down", "ln_attn",
                               "ln_mlp"),
    SPARSE: MIXER_LEAVES + ("gate", "up", "down", "ln_attn", "ln_mlp"),
}
OUTER_LEAVES = ("embed", "final_norm", "head")
FAULTS = (None, "no_selection", "state_dropped", "selection_bf16")


@dataclass(frozen=True)
class Widths:
    """The sizes of a configuration file, under its published keys; the
    sparse layers' sizes come from its ``sparse_config`` group."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    rms_norm_eps: float
    mixer_types: tuple
    scale_emb: float
    scale_depth: float
    mup_denominator: int
    dim_model_base: int
    kernel_size: int
    kernel_stride: int
    block_size: int
    topk: int
    init_blocks: int
    window_size: int
    dense_len: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Widths":
        if (cfg["lightning_nh"], cfg["lightning_nkv"],
                cfg["lightning_head_dim"]) != (
                    cfg["num_attention_heads"], cfg["num_attention_heads"],
                    cfg["head_dim"]):
            raise ValueError("lightning heads are the attention heads' "
                             "number and size in every published config")
        if len(cfg["mixer_types"]) != cfg["num_hidden_layers"]:
            raise ValueError("mixer_types names another number of layers")
        flat = {**cfg, **cfg["sparse_config"],
                "mixer_types": tuple(cfg["mixer_types"])}
        return cls(**{k: flat[k] for k in cls.__dataclass_fields__})

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.mup_denominator ** 0.5

    def layer_shapes(self, kind: str) -> dict[str, tuple[int, ...]]:
        d, f, hd = self.hidden_size, self.intermediate_size, self.head_dim
        q = self.num_attention_heads * hd
        kv = q if kind == LIGHTNING else self.num_key_value_heads * hd
        shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                  "wg": (d, q), "q_norm": (hd,), "k_norm": (hd,),
                  "o_norm": (q,), "gate": (d, f), "up": (d, f),
                  "down": (f, d), "ln_attn": (d,), "ln_mlp": (d,)}
        return {n: shapes[n] for n in LAYER_LEAVES[kind]}

    def outer_shapes(self) -> dict[str, tuple[int, ...]]:
        d, v = self.hidden_size, self.vocab_size
        return {"embed": (v, d), "final_norm": (d,), "head": (d, v)}


# ---------------------------------------------------------------------------
# Weights from the seed: a matrix N(0, 1/fan_in), a gain 1 + 0.1 N(0, 1),
# as the Mistral configurations draw them
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("w", "kind"))
def _layer_weights(base_key, layer, w: Widths, kind: str):
    """Every leaf of a layer of ``kind``; ``layer`` is traced, so a kind
    compiles once however many layers have it."""
    key = jax.random.fold_in(base_key, layer + 1)
    return {n: _leaf(jax.random.fold_in(key, i), n, w.layer_shapes(kind)[n])
            for i, n in enumerate(LAYER_LEAVES[kind])}


@functools.partial(jax.jit, static_argnames=("w", "name"))
def _outer_leaf(base_key, w: Widths, name: str):
    key = jax.random.fold_in(base_key, 0)
    return _leaf(jax.random.fold_in(key, OUTER_LEAVES.index(name)), name,
                 w.outer_shapes()[name])


def outer_leaf(seed: int, w: Widths, name: str):
    return _outer_leaf(jax.random.PRNGKey(seed), w, name)


def layer_weights(seed: int, w: Widths, layer: int) -> dict:
    return _layer_weights(jax.random.PRNGKey(seed), layer, w,
                          w.mixer_types[layer])


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


def _heads(h, lw, w: Widths, n_kv: int, mode: str):
    t, hd = h.shape[0], w.head_dim
    q = mm(h, lw["wq"], mode).reshape(t, w.num_attention_heads, hd)
    k = mm(h, lw["wk"], mode).reshape(t, n_kv, hd)
    v = mm(h, lw["wv"], mode).reshape(t, n_kv, hd)
    return (rms_norm(q, lw["q_norm"], w.rms_norm_eps),
            rms_norm(k, lw["k_norm"], w.rms_norm_eps), v)


def lightning_mixer(h, lw, w: Widths, mode: str, restart_at=None):
    """h [T, d] → [T, d]: the recurrence, token by token. ``restart_at``
    zeroes the state before that position's token enters it."""
    t, hd, nh = h.shape[0], w.head_dim, w.num_attention_heads
    q, k, v = _heads(h, lw, w, nh, mode)
    q, k = _rope(q, w.rope_theta), _rope(k, w.rope_theta)
    lam = jnp.exp(-jnp.exp2(
        -8.0 * jnp.arange(1, nh + 1, dtype=jnp.float32) / nh))

    def token(state, xs):
        qt, kt, vt, at = xs
        if restart_at is not None:
            state = jnp.where(at == restart_at, 0.0, state)
        state = lam[:, None, None] * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.sum(qt[:, :, None] * state, axis=1) * hd ** -0.5

    _, o = jax.lax.scan(token, jnp.zeros((nh, hd, hd), jnp.float32),
                        (q, k, v, jnp.arange(t)), unroll=4)
    o = rms_norm(o.reshape(t, nh * hd), lw["o_norm"], w.rms_norm_eps)
    return mm(o * jax.nn.sigmoid(mm(h, lw["wg"], mode)), lw["wo"], mode)


def _overlapping_windows(w: Widths, n_blocks: int, n_windows: int):
    """(index [NB, C], there [NB, C]): the windows whose tokens
    [stride*j, stride*j + kernel) meet block b's [block*b, block*(b+1))."""
    b = np.arange(n_blocks)
    first = np.maximum(
        -(-(w.block_size * b - w.kernel_size + 1) // w.kernel_stride), 0)
    last = (w.block_size * b + w.block_size - 1) // w.kernel_stride
    count = int((last - first).max()) + 1
    index = first[:, None] + np.arange(count)[None, :]
    there = (index <= last[:, None]) & (index < n_windows)
    return np.minimum(index, max(n_windows - 1, 0)), there


def selected_blocks(qb, pos, ckeys, n_blocks: int, w: Widths, mode: str,
                    fault: str | None = None):
    """Which blocks each query reads: qb [Q, Hkv, G, hd] at positions
    ``pos`` [Q], ckeys [W, Hkv, hd] → bool [Q, Hkv, NB] for a row of
    ``n_blocks``. A dense query reads every block it can see."""
    n_windows = ckeys.shape[0]
    blocks = jnp.arange(n_blocks)
    sees = blocks[None, :] <= (pos // w.block_size)[:, None]  # [Q, NB]
    forced = (blocks[None, :] < w.init_blocks) | (
        blocks[None, :] >= (jnp.maximum(pos - w.window_size + 1, 0)
                            // w.block_size)[:, None])
    forced = (forced & sees)[:, None, :]
    if fault == "no_selection":
        chosen = forced
    else:
        ends = w.kernel_size + w.kernel_stride * jnp.arange(n_windows)
        complete = ends[None, :] <= (pos + 1)[:, None]  # [Q, W]
        scores = _einsum("qkgd,wkd->qkgw", qb, ckeys, mode) \
            * w.head_dim ** -0.5
        scores = jnp.where(complete[:, None, None, :], scores, -jnp.inf)
        p = jnp.where(complete[:, None, None, :],
                      jax.nn.softmax(scores, axis=-1), 0.0)
        g = p.sum(axis=2)  # [Q, Hkv, W]
        index, there = _overlapping_windows(w, n_blocks, n_windows)
        block_score = jnp.where(there[None, None], g[:, :, index],
                                -1.0).max(axis=-1)  # [Q, Hkv, NB]
        rank = jnp.where(forced, jnp.inf, block_score)
        rank = jnp.where(sees[:, None, :], rank, -jnp.inf)
        place = jnp.argsort(jnp.argsort(-rank, axis=-1, stable=True),
                            axis=-1, stable=True)
        chosen = (place < w.topk) & sees[:, None, :]
    dense = (pos + 1 <= w.dense_len)[:, None, None]
    return jnp.where(dense, sees[:, None, :], chosen)


def compressed_keys(k, w: Widths):
    """k [T, Hkv, hd] → the mean key of every whole window [W, Hkv, hd]."""
    n_windows = (k.shape[0] - w.kernel_size) // w.kernel_stride + 1
    starts = w.kernel_stride * jnp.arange(n_windows)
    return jnp.mean(k[starts[:, None] + jnp.arange(w.kernel_size)[None, :]],
                    axis=1)


def sparse_mixer(h, lw, w: Widths, mode: str, fault: str | None = None):
    """h [T, d] → [T, d], T a multiple of the block: queries a block at a
    time, each scoring every key of the row under its mask."""
    t, hd = h.shape[0], w.head_dim
    nh, nkv = w.num_attention_heads, w.num_key_value_heads
    q, k, v = _heads(h, lw, w, nkv, mode)
    ckeys = compressed_keys(k, w)
    scoring = "bf16" if fault == "selection_bf16" else mode
    qb = q.reshape(t // w.block_size, w.block_size, nkv, nh // nkv, hd)
    key_block = jnp.arange(t) // w.block_size

    def queries(xs):
        qs, pos = xs
        reads = selected_blocks(qs, pos, ckeys, t // w.block_size, w,
                                scoring, fault)
        mask = reads[:, :, key_block] & (
            jnp.arange(t)[None, None, :] <= pos[:, None, None])
        scores = _einsum("qkgd,tkd->qkgt", qs, k, mode) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(mask[:, :, None, :], scores, -jnp.inf),
                           axis=-1)
        return _einsum("qkgt,tkd->qkgd", p, v, mode)

    o = jax.lax.map(queries, (qb, jnp.arange(t).reshape(-1, w.block_size)))
    o = o.reshape(t, nh * hd)
    return mm(o * jax.nn.sigmoid(mm(h, lw["wg"], mode)), lw["wo"], mode)


def mlp(h, lw, w: Widths, mode: str):
    """SwiGLU on h [T, d], ``16 * block`` tokens at a time."""
    t, d = h.shape
    step = min(t, 16 * w.block_size)

    def tokens(hs):
        gated = jax.nn.silu(mm(hs, lw["gate"], mode)) * mm(hs, lw["up"], mode)
        return mm(gated, lw["down"], mode)

    return jax.lax.map(tokens, h.reshape(t // step, step, d)).reshape(t, d)


def layer(x, lw, w: Widths, kind: str, mode: str = "f32",
          fault: str | None = None, fault_at=None):
    """One decoder layer on one row x [T, d], causal over T."""
    s = w.residual_scale
    h = rms_norm(x, lw["ln_attn"], w.rms_norm_eps)
    if kind == LIGHTNING:
        mixed = lightning_mixer(
            h, lw, w, mode, fault_at if fault == "state_dropped" else None)
    else:
        mixed = sparse_mixer(h, lw, w, mode, fault)
    x = x + s * mixed
    return x + s * mlp(rms_norm(x, lw["ln_mlp"], w.rms_norm_eps), lw, w, mode)


@functools.partial(jax.jit, static_argnames=("w", "kind", "mode", "fault"))
def _layer_rows(x, lw, fault_at, w: Widths, kind: str, mode: str, fault):
    return jax.lax.map(
        lambda row: layer(row[0], lw, w, kind, mode, fault, row[1]),
        (x, fault_at))


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _logits_at(x, final_norm, head, positions, w: Widths, mode: str):
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    h = rms_norm(picked, final_norm, w.rms_norm_eps)
    return mm(h / (w.hidden_size / w.dim_model_base), head, mode)


def padded_length(w: Widths, longest: int) -> int:
    """Row length the forward wants: whole steps of ``16 * block``."""
    step = 16 * w.block_size
    return -(-longest // step) * step


def logits_at(seed: int, w: Widths, tokens: np.ndarray,
              positions: np.ndarray, mode: str = "f32",
              fault: str | None = None, fault_at=None):
    """Logits [B, P, V] at ``positions`` [B, P] of ``tokens`` [B, T] (T as
    :func:`padded_length` gives it; what follows a row's real tokens is
    padding no earlier position sees), the weights made and dropped one
    layer at a time. ``fault_at`` [B]: see the module docstring."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    b, t = tokens.shape
    if t != padded_length(w, t):
        raise ValueError(f"row length {t} is not a whole number of steps")
    at = jnp.asarray(np.zeros(b, np.int32) if fault_at is None else fault_at)
    x = outer_leaf(seed, w, "embed")[jnp.asarray(tokens)] * w.scale_emb
    for i, kind in enumerate(w.mixer_types):
        x = _layer_rows(x, layer_weights(seed, w, i), at, w, kind, mode,
                        fault)
    return _logits_at(x, outer_leaf(seed, w, "final_norm"),
                      outer_leaf(seed, w, "head"), jnp.asarray(positions),
                      w, mode)


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _selection_flips(x, lw, positions, w: Widths, mode: str):
    nkv, hd = w.num_key_value_heads, w.head_dim
    h = rms_norm(x, lw["ln_attn"], w.rms_norm_eps)
    q, k, _ = _heads(h, lw, w, nkv, "f32")
    ckeys = compressed_keys(k, w)
    qs = q[positions].reshape(len(positions), nkv, -1, hd)
    n_blocks = x.shape[0] // w.block_size
    exact = selected_blocks(qs, positions, ckeys, n_blocks, w, "f32")
    rounded = selected_blocks(qs, positions, ckeys, n_blocks, w, mode)
    swapped = (exact != rounded).sum(axis=-1) // 2  # [P, Hkv]
    sparse = (positions + 1 > w.dense_len)[:, None]
    return {"selections": sparse.sum() * nkv,
            "differing": ((swapped > 0) & sparse).sum(),
            "blocks_swapped": (swapped * sparse).sum()}


def selection_flips(seed: int, w: Widths, tokens: np.ndarray,
                    positions: np.ndarray, mode: str = "bf16") -> dict:
    """How often block scores computed in ``mode`` pick other blocks than
    float32 scores do, for the queries at ``positions`` [P] of one row
    ``tokens`` [T], on the first sparse layer's own float32 inputs:
    selections (query x KV head, contexts over dense_len only), how many
    differ, and blocks swapped in all."""
    first = w.mixer_types.index(SPARSE)
    x = outer_leaf(seed, w, "embed")[jnp.asarray(tokens)][None] * w.scale_emb
    at = jnp.zeros((1,), jnp.int32)
    for i in range(first):
        x = _layer_rows(x, layer_weights(seed, w, i), at, w,
                        w.mixer_types[i], "f32", None)
    out = _selection_flips(x[0], layer_weights(seed, w, first),
                           jnp.asarray(positions), w, mode)
    return {k: int(v) for k, v in out.items()}
