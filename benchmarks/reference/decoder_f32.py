"""Plain reference of the pre-norm decoder block the benchmark's
configurations describe (RMSNorm, rotary in the half-split convention,
grouped-query causal attention, SwiGLU, untied head): forward, next-token
loss with z-loss, gradients layer by layer, and the Adafactor update the
trainer's configuration states. Straight jax.numpy in float32 at "highest"
matmul precision, no kernels, no cache, no batching tricks.

It imports nothing of the program and takes nothing the program made: the
weights come from :func:`outer_weights` / :func:`layer_weights`, which the
harness also uses (stacked) to fill the program. ``mode`` selects the
matmul precision: "f32" is the reference, "bf16" what the configurations
state, "int8" the control (the nearest precision below bf16: operands
rounded to 8 bits with one scale per row, straight-through gradients).

Memory: weights are made and used one layer at a time when serving, and
gradients are taken one layer at a time (a forward that keeps the layer
inputs, then a vjp per layer over rows of the batch) when training, so the
real sizes fit one 16 GB chip beside nothing else.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "gate", "up", "down",
                "ln_attn", "ln_mlp")
OUTER_LEAVES = ("embed", "final_norm", "head")


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

    @classmethod
    def from_config(cls, cfg: dict) -> "Widths":
        return cls(**{k: cfg[k] for k in cls.__dataclass_fields__})

    def layer_shapes(self) -> dict[str, tuple[int, ...]]:
        d, f = self.hidden_size, self.intermediate_size
        q = self.num_attention_heads * self.head_dim
        kv = self.num_key_value_heads * self.head_dim
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                "gate": (d, f), "up": (d, f), "down": (f, d),
                "ln_attn": (d,), "ln_mlp": (d,)}

    def outer_shapes(self) -> dict[str, tuple[int, ...]]:
        d, v = self.hidden_size, self.vocab_size
        return {"embed": (v, d), "final_norm": (d,), "head": (d, v)}


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def _leaf(key, name, shape):
    """A matrix is N(0, 1/fan_in); a norm gain is 1 + 0.1·N(0, 1)."""
    n = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        return 1.0 + 0.1 * n
    fan_in = shape[1] if name == "embed" else shape[0]
    return n * (fan_in ** -0.5)


@functools.partial(jax.jit, static_argnames=("w", "layer"))
def _layer_weights(base_key, w: Widths, layer: int):
    key = jax.random.fold_in(base_key, layer + 1)
    return {n: _leaf(jax.random.fold_in(key, i), n, w.layer_shapes()[n])
            for i, n in enumerate(LAYER_LEAVES)}


def _outer(base_key, w: Widths):
    key = jax.random.fold_in(base_key, 0)
    return {n: _leaf(jax.random.fold_in(key, i), n, w.outer_shapes()[n])
            for i, n in enumerate(OUTER_LEAVES)}


_outer_weights = jax.jit(_outer, static_argnames=("w",))


def layer_weights(seed: int, w: Widths, layer: int) -> dict:
    return _layer_weights(jax.random.PRNGKey(seed), w, layer)


def outer_weights(seed: int, w: Widths) -> dict:
    return _outer_weights(jax.random.PRNGKey(seed), w)


@functools.partial(jax.jit, static_argnames=("w",))
def _stacked_weights(base_key, w: Widths):
    n_layers = w.num_hidden_layers
    layer_keys = jnp.stack([jax.random.fold_in(base_key, i + 1)
                            for i in range(n_layers)])
    layers = {}
    for i, name in enumerate(LAYER_LEAVES):
        shape = w.layer_shapes()[name]
        layers[name] = jax.vmap(
            lambda k, i=i, name=name, shape=shape: _leaf(
                jax.random.fold_in(k, i), name, shape))(layer_keys)
    return {"outer": _outer(base_key, w),
            "layers": layers}


def stacked_weights(seed: int, w: Widths) -> dict:
    """All weights in one jitted call, each layer leaf stacked on a leading
    layer axis: the same numbers :func:`layer_weights` gives layer by
    layer. This is what the harness hands the program."""
    return _stacked_weights(jax.random.PRNGKey(seed), w)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fake_quant(x, axis):
    """Round to 8 bits with one scale per vector along ``axis``; the
    gradient passes straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(x, wgt, mode: str):
    """x [..., k] @ wgt [k, n] in the precision ``mode`` names."""
    if mode == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), wgt.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "int8":
        x, wgt = _fake_quant(x, -1), _fake_quant(wgt, 0)
    elif mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}")
    return jnp.matmul(x, wgt, precision=HIGHEST)


def _einsum(spec, a, b, mode: str):
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "int8":
        a, b = _fake_quant(a, -1), _fake_quant(b, -1)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, gain, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_tables(w: Widths, length: int):
    inv = 1.0 / (w.rope_theta ** (
        jnp.arange(0, w.head_dim, 2, dtype=jnp.float32) / w.head_dim))
    ang = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv)
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """x [B, T, H, hd]: rotate the pairs (x[..., i], x[..., i + hd/2])."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def block(x, lw: dict, w: Widths, mode: str = "f32"):
    """One decoder layer on x [B, T, d], causal over T."""
    b, t, _ = x.shape
    hq, hkv, hd = w.num_attention_heads, w.num_key_value_heads, w.head_dim
    cos, sin = rope_tables(w, t)
    h = rms_norm(x, lw["ln_attn"], w.rms_norm_eps)
    q = _rope(mm(h, lw["wq"], mode).reshape(b, t, hq, hd), cos, sin)
    k = _rope(mm(h, lw["wk"], mode).reshape(b, t, hkv, hd), cos, sin)
    v = mm(h, lw["wv"], mode).reshape(b, t, hkv, hd)
    qg = q.reshape(b, t, hkv, hq // hkv, hd)
    scores = _einsum("bskgd,btkd->bkgst", qg, k, mode) * hd ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    ctx = _einsum("bkgst,btkd->bskgd", probs, v, mode)
    x = x + mm(ctx.reshape(b, t, hq * hd), lw["wo"], mode)
    h = rms_norm(x, lw["ln_mlp"], w.rms_norm_eps)
    gated = jax.nn.silu(mm(h, lw["gate"], mode)) * mm(h, lw["up"], mode)
    return x + mm(gated, lw["down"], mode)


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _block_rows(x, lw, w: Widths, mode: str):
    """:func:`block` one row of the batch at a time (bounded memory)."""
    return jax.lax.map(lambda row: block(row[None], lw, w, mode)[0], x)


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _logits_at(x, outer, positions, w: Widths, mode: str):
    """Final norm and head at ``positions`` [B, P] of x [B, T, d]."""
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    h = rms_norm(picked, outer["final_norm"], w.rms_norm_eps)
    return mm(h, outer["head"], mode)


def logits_at(seed: int, w: Widths, tokens: np.ndarray,
              positions: np.ndarray, mode: str = "f32"):
    """Logits [B, P, V] at ``positions`` [B, P] of ``tokens`` [B, T], the
    weights made and dropped one layer at a time."""
    outer = outer_weights(seed, w)
    x = outer["embed"][jnp.asarray(tokens)]
    for i in range(w.num_hidden_layers):
        x = _block_rows(x, layer_weights(seed, w, i), w, mode)
    return _logits_at(x, outer, jnp.asarray(positions), w, mode)


# ---------------------------------------------------------------------------
# Loss and gradients, layer by layer
# ---------------------------------------------------------------------------


def _head_loss(final_norm, head, x, targets, w: Widths, z_loss: float,
               mode: str):
    logits = mm(rms_norm(x, final_norm, w.rms_norm_eps), head, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    nll = jnp.mean(lse - picked)
    return nll + z_loss * jnp.mean(lse * lse), nll


@functools.partial(jax.jit, static_argnames=("w", "z_loss", "mode"))
def _head_grads(final_norm, head, x, targets, w, z_loss, mode):
    (_, nll), grads = jax.value_and_grad(
        _head_loss, argnums=(0, 1, 2), has_aux=True)(
            final_norm, head, x, targets, w, z_loss, mode)
    return nll, grads


@functools.partial(jax.jit, static_argnames=("w", "mode"))
def _block_grads(x, lw, dy, w: Widths, mode: str):
    """vjp of :func:`block` row by row: (sum of the rows' weight
    gradients, dx [B, T, d])."""
    def one(acc, row):
        xr, dyr = row
        _, vjp = jax.vjp(lambda a, p: block(a[None], p, w, mode)[0], xr, lw)
        dxr, g = vjp(dyr)
        return jax.tree.map(jnp.add, acc, g), dxr
    zeros = jax.tree.map(jnp.zeros_like, lw)
    return jax.lax.scan(one, zeros, (x, dy))


@functools.partial(jax.jit, static_argnames=("vocab",))
def _embed_grads(tokens, dx, vocab: int):
    flat = dx.reshape(-1, dx.shape[-1])
    return jnp.zeros((vocab, dx.shape[-1]), jnp.float32).at[
        tokens.reshape(-1)].add(flat)


def loss_and_grads(outer: dict, layers: list[dict], tokens: np.ndarray,
                   w: Widths, *, z_loss: float, mode: str = "f32"):
    """tokens [B, T+1] → (mean next-token nll, gradients of nll + z-loss
    as ``{"outer": {...}, "layers": [...]}``)."""
    inputs = jnp.asarray(tokens[:, :-1])
    targets = jnp.asarray(tokens[:, 1:])
    xs = [outer["embed"][inputs]]
    for lw in layers:
        xs.append(_block_rows(xs[-1], lw, w, mode))
    nll, (g_norm, g_head, dx) = _head_grads(
        outer["final_norm"], outer["head"], xs.pop(), targets, w, z_loss,
        mode)
    g_layers = [None] * len(layers)
    for i in reversed(range(len(layers))):
        g_layers[i], dx = _block_grads(xs.pop(), layers[i], dx, w, mode)
    g_embed = _embed_grads(inputs, dx, w.vocab_size)
    return nll, {"outer": {"embed": g_embed, "final_norm": g_norm,
                           "head": g_head}, "layers": g_layers}


# ---------------------------------------------------------------------------
# Adafactor as the trainer's configuration states it
# ---------------------------------------------------------------------------
#
# optax.chain(clip_by_global_norm(c), adafactor(lr(t), min_dim 128)):
# factored second moments with decay 1 - (t+1)^-0.8, the update clipped to
# unit RMS per block, times the learning rate, times the block's parameter
# RMS (at least 1e-3). A "block" is a leaf of the program's tree, and the
# program stacks each layer leaf on a leading layer axis: the second
# moments are per layer, the two RMS values are over all layers of a kind.


def learning_rate(step: int, opt: dict) -> float:
    """Linear warm-up from 0, then cosine decay to ``min_lr_ratio``."""
    peak, warm = opt["learning_rate"], opt["warmup_steps"]
    if step < warm:
        return peak * step / warm
    total = max(opt["total_steps"], warm + 1)
    frac = min((step - warm) / (total - warm), 1.0)
    alpha = opt["min_lr_ratio"]
    return peak * ((1 - alpha) * 0.5 * (1 + math.cos(math.pi * frac)) + alpha)


def factored_axes(shape, min_dim: int, stacked: int):
    """(row axis, col axis) of the per-layer array that Adafactor reduces
    over, or None. ``stacked`` is the layer count riding the leaf's
    leading axis in the program (0 for none)."""
    full = ((stacked,) if stacked else ()) + tuple(shape)
    if len(full) < 2:
        return None
    order = np.argsort(full)
    if full[order[-2]] < min_dim:
        return None
    off = 1 if stacked else 0
    d1, d0 = int(order[-2]) - off, int(order[-1]) - off
    if d1 < 0 or d0 < 0:
        raise ValueError(f"layer axis of {full} would be factored")
    return d1, d0


@functools.partial(jax.jit, static_argnames=("axes",),
                   donate_argnums=(0, 2))
def _adafactor_block(params, grads, moments, decay, lr, clip_scale,
                     axes):
    """One program leaf = a tuple of per-layer arrays. Returns (params,
    moments) after one update."""
    eps = 1e-30
    updates, new_moments = [], []
    for g, m in zip(grads, moments):
        g = g * clip_scale
        sq = g * g + eps
        if axes is not None:
            d1, d0 = axes
            v_row = decay * m[0] + (1 - decay) * jnp.mean(sq, axis=d0)
            v_col = decay * m[1] + (1 - decay) * jnp.mean(sq, axis=d1)
            row = (v_row / jnp.mean(v_row)) ** -0.5
            u = (g * jnp.expand_dims(row, d0)
                 * jnp.expand_dims(v_col ** -0.5, d1))
            new_moments.append((v_row, v_col))
        else:
            v = decay * m[0] + (1 - decay) * sq
            u = g * v ** -0.5
            new_moments.append((v,))
        updates.append(u)
    count = sum(u.size for u in updates)
    u_rms = jnp.sqrt(sum(jnp.sum(u * u) for u in updates) / count)
    p_rms = jnp.sqrt(sum(jnp.sum(p * p) for p in params) / count)
    scale = lr * jnp.maximum(p_rms, 1e-3) / jnp.maximum(1.0, u_rms)
    return (tuple(p - scale * u for p, u in zip(params, updates)),
            tuple(new_moments))


@jax.jit
def _sq_norm(x):
    return jnp.sum(x * x)


def _blocks(outer: dict, layers: list[dict]):
    """The program's leaves as (name, per-layer arrays, stacked count)."""
    for name in OUTER_LEAVES:
        yield name, (outer[name],), 0
    for name in LAYER_LEAVES:
        yield name, tuple(lw[name] for lw in layers), len(layers)


def _per_leaf(outer, layers, fn) -> dict[str, float]:
    out = {}
    for name, arrays, stacked in _blocks(outer, layers):
        for i, a in enumerate(arrays):
            out[f"layers.{i}.{name}" if stacked else name] = fn(a)
    return out


def train_readings(seed: int, w: Widths, batches: list[np.ndarray],
                   opt: dict, *, z_loss: float, mode: str = "f32",
                   half_batch: bool = False) -> dict:
    """Follow ``len(batches)`` optimizer steps from the seed's weights.
    Returns each step's nll and global gradient norm, the first
    gradient's norm per leaf (as the optimizer gets it, before its
    clipping), and the norm of each leaf's change over all the steps.
    ``half_batch`` plants the fault of a step that drops the second half
    of its rows."""
    outer = dict(outer_weights(seed, w))
    layers = [dict(layer_weights(seed, w, i))
              for i in range(w.num_hidden_layers)]
    moments: dict[str, tuple] = {}
    out = {"loss": [], "grad_norm": [], "first_grad": {}, "change": {}}
    for step, tokens in enumerate(batches):
        if half_batch:
            tokens = tokens[: max(1, len(tokens) // 2)]
        nll, grads = loss_and_grads(outer, layers, tokens, w,
                                    z_loss=z_loss, mode=mode)
        sq = _per_leaf(grads["outer"], grads["layers"],
                       lambda a: float(_sq_norm(a)))
        gnorm = math.sqrt(sum(sq.values()))
        out["loss"].append(float(nll))
        out["grad_norm"].append(gnorm)
        if step == 0:
            out["first_grad"] = {k: math.sqrt(v) for k, v in sq.items()}
        clip = opt["grad_clip_norm"]
        clip_scale = min(1.0, clip / gnorm) if clip else 1.0
        decay = 1.0 - (step + 1.0) ** -0.8
        lr = learning_rate(step, opt)
        g_blocks = {n: a for n, a, _ in _blocks(grads["outer"],
                                                grads["layers"])}
        del grads
        for name, arrays, stacked in _blocks(outer, layers):
            axes = factored_axes(arrays[0].shape, 128, stacked)
            if name not in moments:
                moments[name] = tuple(
                    (jnp.zeros(np.delete(a.shape, axes[1]), jnp.float32),
                     jnp.zeros(np.delete(a.shape, axes[0]), jnp.float32))
                    if axes is not None else (jnp.zeros_like(a),)
                    for a in arrays)
            new, moments[name] = _adafactor_block(
                arrays, g_blocks.pop(name), moments[name], decay, lr,
                clip_scale, axes)
            if stacked:
                for lw, a in zip(layers, new):
                    lw[name] = a
            else:
                outer[name] = new[0]
    start_outer = outer_weights(seed, w)
    for name in OUTER_LEAVES:
        out["change"][name] = math.sqrt(float(_sq_norm(
            outer[name] - start_outer[name])))
    del start_outer
    for i, lw in enumerate(layers):
        start = layer_weights(seed, w, i)
        for name in LAYER_LEAVES:
            out["change"][f"layers.{i}.{name}"] = math.sqrt(float(
                _sq_norm(lw[name] - start[name])))
    return out
