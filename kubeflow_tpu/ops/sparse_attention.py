"""Block-sparse attention over a cache (InfLLM-v2): a query reads block 0,
the blocks that hold its last ``window`` tokens, and the best of the rest
by a score over *compressed keys*, up to ``topk`` blocks in all; a query
whose context is no longer than ``dense_len`` reads everything.

    compressed key  Kc_j = mean(K[stride*j : stride*j + kernel])   per KV head
    p_{h,j} = softmax_j(q_h . Kc_j / sqrt(hd))     over the windows that end
                                                   at or before the query
    g_j     = sum of p_{h,j} over the KV head's query heads
    score_b = max of g_j over the windows that overlap block b

One selection per (query, KV head), shared by the head's group. The pieces
are plain XLA on one layout, keys and values head-major
``[..., Hkv, tokens, hd]``:

- :func:`compress_keys` / :func:`compress_last`: all windows of a row, or
  the one window a decoded token completes;
- :func:`select_blocks`: block indices and which of them count. A dense
  query is a query whose every visible block is forced, so rows on both
  sides of ``dense_len`` share one compiled shape;
- :func:`attend_selected`: softmax attention over gathered blocks (decode);
- :func:`attend_span`: the same selection as a mask over a whole row, in
  blocks of queries (prefill, and the cache-free forward).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax import lax

_BIG = 1e30


@dataclass(frozen=True)
class SparseSpec:
    """The sizes of the selection, in tokens but for ``topk`` and
    ``init_blocks`` (blocks)."""

    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    def __post_init__(self):
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError(
                f"sparse attention: kernel {self.kernel} and block "
                f"{self.block} must be multiples of stride {self.stride}")
        forced = self.init_blocks + self.window // self.block + 1
        if forced > self.topk:
            raise ValueError(
                f"sparse attention: {self.init_blocks} first block(s) and a "
                f"window of {self.window} tokens force up to {forced} "
                f"blocks of {self.block}, more than topk {self.topk}")

    def n_windows(self, total: int) -> int:
        """Compressed keys of a row of ``total`` tokens."""
        return max(0, (total - self.kernel) // self.stride + 1)

    def n_select(self, total: int) -> int:
        """Blocks gathered per query: ``topk``, or every block of a dense
        context if that is more, never more than the row has."""
        n_blocks = -(-total // self.block)
        return min(n_blocks, max(self.topk, -(-self.dense_len // self.block)))


def compress_keys(k_row, spec: SparseSpec):
    """Every window of a row: k_row [B, Hkv, T, hd], T a multiple of the
    stride → [B, Hkv, W, hd] in k_row's dtype (means taken in float32).
    A window that reaches past the row's real tokens holds junk; what reads
    them goes by position (:func:`select_blocks`)."""
    b, h, t, hd = k_row.shape
    w = spec.n_windows(t)
    sums = k_row.astype(jnp.float32).reshape(
        b, h, t // spec.stride, spec.stride, hd).sum(axis=3)
    out = sum(sums[:, :, o:o + w] for o in range(spec.kernel // spec.stride))
    return (out / spec.kernel).astype(k_row.dtype)


def compress_last(k_last):
    """The window its last token completes: k_last [B, Hkv, kernel, hd] →
    [B, Hkv, hd]."""
    return k_last.astype(jnp.float32).mean(axis=2).astype(k_last.dtype)


def select_blocks(q, ckeys, pos, n_blocks: int, spec: SparseSpec):
    """q [B, Hkv, G, Q, hd] at positions ``pos`` [B, Q]; ckeys
    [B, Hkv, W, hd]. Returns (idx [B, Hkv, Q, n] int32 block indices, ok
    [B, Hkv, Q, n] bool) with n = ``spec.n_select``: the blocks to read,
    the forced ones first, then by score; a slot that is not ``ok`` is
    padding."""
    hd = q.shape[-1]
    w = ckeys.shape[2]
    scores = jnp.einsum("bkgqd,bkwd->bkgqw", q, ckeys,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    ends = spec.kernel + spec.stride * jnp.arange(w)
    visible = ends[None, None, :] <= (pos + 1)[:, :, None]  # [B, Q, W]
    visible = visible[:, None, None]
    top = jnp.max(jnp.where(visible, scores, -_BIG), axis=-1, keepdims=True)
    e = jnp.where(visible, jnp.exp(scores - top), 0.0)
    p = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    g = p.sum(axis=2)  # [B, Hkv, Q, W]
    # Block b overlaps the windows r*b - lead .. r*b + r - 1.
    r = spec.block // spec.stride
    lead = (spec.kernel - 1) // spec.stride
    gp = jnp.pad(g, ((0, 0), (0, 0), (0, 0),
                     (lead, max(0, r * n_blocks + r - w))),
                 constant_values=-1.0)
    block_score = gp[..., 0:r * n_blocks:r]
    for o in range(1, lead + r):
        block_score = jnp.maximum(block_score,
                                  gp[..., o:o + r * n_blocks:r])
    blk = jnp.arange(n_blocks)[None, None, :]
    p_ = pos[:, :, None]
    forced = (blk < spec.init_blocks) | (
        blk >= jnp.maximum(p_ - spec.window + 1, 0) // spec.block)
    dense = (p_ + 1) <= spec.dense_len
    rank = jnp.where((forced | dense)[:, None], _BIG, block_score)
    rank = jnp.where((blk <= p_ // spec.block)[:, None], rank, -_BIG)
    n = spec.n_select(n_blocks * spec.block)
    vals, idx = lax.top_k(rank, n)
    ok = (vals > -_BIG / 2) & (dense[:, None] | (jnp.arange(n) < spec.topk))
    return idx.astype(jnp.int32), ok


def _softmax_pv(scores, mask, v, out_dtype, pv: str):
    scores = jnp.where(mask, scores, -_BIG)
    top = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(scores - top), 0.0)
    p = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum(pv, p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32
                      ).astype(out_dtype)


def attend_selected(q, k_sel, v_sel, idx, ok, pos, spec: SparseSpec):
    """One query per row over its gathered blocks. q [B, Hkv, G, hd];
    k_sel, v_sel [B, Hkv, n, block, hd] (the blocks ``idx`` [B, Hkv, n]
    names, ``ok`` marking the real ones); pos [B]. → [B, Hkv, G, hd]."""
    b, h, n, bs, hd = k_sel.shape
    tok = idx[..., None] * spec.block + jnp.arange(bs)  # [B, Hkv, n, bs]
    mask = ok[..., None] & (tok <= pos[:, None, None, None])
    mask = mask.reshape(b, h, 1, n * bs)
    k_sel = k_sel.reshape(b, h, n * bs, hd)
    v_sel = v_sel.reshape(b, h, n * bs, hd)
    scores = jnp.einsum("bkgd,bktd->bkgt", q, k_sel,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    return _softmax_pv(scores, mask, v_sel, q.dtype, "bkgt,bktd->bkgd")


def attend_span(q, pos, k_row, v_row, ckeys, spec: SparseSpec,
                q_block: int = 128):
    """A span of queries over a whole row, the selection as a mask.
    q [B, S, H, hd] at positions ``pos`` [B, S]; k_row, v_row
    [B, Hkv, T, hd] with T a multiple of the block; ckeys [B, Hkv, W, hd].
    → [B, S, H, hd]. Queries go ``q_block`` at a time so that the scores
    over the row stay bounded."""
    b, s, h, hd = q.shape
    hkv, t = k_row.shape[1], k_row.shape[2]
    n_blocks = t // spec.block
    qb = min(q_block, s)
    pad = -s % qb
    qg = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, (s + pad) // qb, qb, hkv, h // hkv, hd).transpose(1, 0, 3, 4, 2, 5)
    posb = jnp.pad(pos, ((0, 0), (0, pad))).reshape(
        b, (s + pad) // qb, qb).transpose(1, 0, 2)
    tok_block = jnp.arange(t) // spec.block

    def one(xs):
        qc, pc = xs  # [B, Hkv, G, qb, hd], [B, qb]
        idx, ok = select_blocks(qc, ckeys, pc, n_blocks, spec)
        chosen = ((idx[..., None] == jnp.arange(n_blocks)) & ok[..., None]
                  ).any(axis=-2)  # [B, Hkv, qb, NB]
        mask = chosen[..., tok_block] & (
            jnp.arange(t)[None, None, None, :] <= pc[:, None, :, None])
        scores = jnp.einsum("bkgqd,bktd->bkgqt", qc, k_row,
                            preferred_element_type=jnp.float32) * hd ** -0.5
        return _softmax_pv(scores, mask[:, :, None], v_row, q.dtype,
                           "bkgqt,bktd->bkgqd")

    out = lax.map(one, (qg, posb))  # [n, B, Hkv, G, qb, hd]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, s + pad, h, hd)
    return out[:, :s]
