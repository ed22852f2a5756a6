"""Decayed linear attention (Lightning Attention), per head:

    S_t = lam * S_{t-1} + k_t^T v_t          (state [hd, hd], float32)
    o_t = (q_t / sqrt(hd)) S_t = sum_{j<=t} lam^(t-j) (q_t.k_j / sqrt(hd)) v_j

with one decay per head, ``lam_h = exp(-2^(-8h/H))``, h = 1..H. Two forms of
the same equation: :func:`lightning_step` advances the state by one token
(decode: the state is read and written once, nothing grows with the
context), :func:`lightning_chunked` runs a span of tokens in chunks of
``chunk`` (prefill: the quadratic form inside a chunk, the state between
chunks). Plain XLA; the state and every product with it stay in float32,
and the float32 contractions ask for full precision (they are small next
to the projections).

Every decay is written as ``exp(-slope * distance)`` with the distance
never negative, so nothing overflows whatever the chunk width.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def lightning_slopes(n_heads: int):
    """``-log(lam_h)`` for h = 1..n_heads: ``2^(-8h/n_heads)``, float32."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / n_heads)


def lightning_step(q, k, v, state, slopes):
    """One token per row. q, k, v: [B, H, hd]; state: [B, H, hd, hd]
    float32. Returns (o [B, H, hd] float32, new state)."""
    hd = q.shape[-1]
    q32, k32, v32 = (a.astype(jnp.float32) for a in (q, k, v))
    lam = jnp.exp(-slopes)[None, :, None, None]
    state = lam * state + k32[..., :, None] * v32[..., None, :]
    o = jnp.einsum("bhd,bhde->bhe", q32 * hd ** -0.5, state,
                   precision=_HIGHEST)
    return o, state


def lightning_chunked(q, k, v, state, slopes, n_valid=None, chunk: int = 128):
    """A span of tokens in chunks. q, k, v: [B, S, H, hd]; state
    [B, H, hd, hd] float32 as the span begins; ``n_valid`` [B]: the
    leading tokens of each row that are real (right padding neither enters
    the state nor decays it; its outputs are junk). Returns
    (o [B, S, H, hd] float32, state after each row's last real token).

    Per chunk of C tokens, with i, j the offsets inside it and m the real
    tokens it holds:
        O_i    = sum_{j<=i} lam^(i-j) (q_i.k_j) v_j / sqrt(hd)
                 + lam^(i+1) q_i S_prev / sqrt(hd)
        S_next = lam^m S_prev + sum_{j<m} lam^(m-1-j) k_j^T v_j
    """
    b, s, h, hd = q.shape
    if n_valid is None:
        n_valid = jnp.full((b,), s, jnp.int32)
    c = min(chunk, s)
    pad = -s % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    n_chunks = (s + pad) // c

    def split(a):  # [B, S, H, hd] -> [n, B, H, C, hd] float32
        a = a.astype(jnp.float32).reshape(b, n_chunks, c, h, hd)
        return a.transpose(1, 0, 3, 2, 4)

    i = jnp.arange(c)
    dist = (i[:, None] - i[None, :]).astype(jnp.float32)
    # [H, C, C]: lam^(i-j) on and below the diagonal, 0 above it.
    intra = jnp.where(dist >= 0,
                      jnp.exp(-slopes[:, None, None] * jnp.abs(dist)), 0.0)
    carry_in = jnp.exp(-slopes[:, None] * (i + 1.0))  # [H, C]: lam^(i+1)
    scale = hd ** -0.5

    def one(state, xs):
        qc, kc, vc, start = xs
        m = jnp.clip(n_valid - start, 0, c)  # [B] real tokens in the chunk
        left = (m[:, None] - 1 - i[None, :]).astype(jnp.float32)  # [B, C]
        # [B, H, C]: lam^(m-1-j) for the real tokens, 0 for the padding.
        to_end = jnp.where(
            left[:, None, :] >= 0,
            jnp.exp(-slopes[None, :, None] * jnp.abs(left)[:, None, :]), 0.0)
        kc = jnp.where(left[:, None, :, None] >= 0, kc, 0.0)
        scores = jnp.einsum("bhid,bhjd->bhij", qc, kc,
                            precision=_HIGHEST) * (intra * scale)
        o = jnp.einsum("bhij,bhjd->bhid", scores, vc, precision=_HIGHEST)
        o = o + jnp.einsum("bhid,bhde->bhie", qc * scale, state,
                           precision=_HIGHEST) * carry_in[None, :, :, None]
        grown = jnp.einsum("bhjd,bhje->bhde", kc * to_end[..., None], vc,
                           precision=_HIGHEST)
        keep = jnp.exp(-slopes[None, :] * m[:, None].astype(jnp.float32))
        return keep[..., None, None] * state + grown, o

    state, o = lax.scan(one, state, (split(q), split(k), split(v),
                                     jnp.arange(n_chunks) * c))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, s + pad, h, hd)
    return o[:, :s], state
