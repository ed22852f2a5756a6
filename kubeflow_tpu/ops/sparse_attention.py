"""Block-sparse attention over a cache (InfLLM-v2): a query reads block 0,
the blocks that hold its last ``window`` tokens and the best of the rest
by a score over *compressed keys*, up to ``topk`` blocks in all; a query
whose context is no longer than ``dense_len`` reads everything.

    compressed key  Kc_j = mean(K[stride*j : stride*j + kernel])   per KV head
    p_{h,j} = softmax_j(q_h . Kc_j / sqrt(hd))     over the windows that end
                                                   at or before the query
    g_j     = sum of p_{h,j} over the KV head's query heads
    score_b = max of g_j over the windows that overlap block b

One selection per (query, KV head), shared by the head's group. Keys and
values are head-major, ``[..., Hkv, tokens, hd]``, in a row and inside a
pool block alike, so one (block, KV head) tile is contiguous.

The selection is plain XLA:

- :func:`compress_keys` / :func:`compress_last`: all windows of a row, or
  the one window a decoded token completes;
- :func:`select_blocks`: block indices and which of them count, the ones
  that count first. A dense query is a query whose every visible block is
  forced, so rows on both sides of ``dense_len`` share one compiled shape.
  The pick is ``lax.top_k`` of the float32 ranks (on a TPU a stable sort
  of every row, NB log^2 NB compare-exchanges a (row, head, query), the
  rows in parallel along the lanes): exact, the forced blocks first, then
  by score, of equal scores the lower block first. Equal scores are
  common: neighbouring blocks share a window of compressed keys, and a
  block's score is a maximum.

What reads the selected blocks is one of three:

- :func:`sparse_decode_attention` (decode, one query a row): on a TPU, at
  lane-aligned tiles, a Pallas kernel that copies each selected
  (block, KV head) tile from the pool WHERE IT LIES into VMEM, by physical
  block id, a chunk of tiles at a time and the next chunk in flight, with an
  online softmax, and stops at the row's own count of blocks. The pool is
  never sliced, gathered or copied. Elsewhere (the CPU, a head size under
  128) an XLA gather of the blocks followed by :func:`attend_selected`,
  which is also what the kernel's tests compare it with;
- :func:`attend_selected`: softmax attention over gathered blocks;
- :func:`attend_span`: the same selection as a mask over a whole row, in
  blocks of queries (prefill, and the cache-free forward).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from kubeflow_tpu.ops.attention import paged_tile_unsupported

_BIG = 1e30
# Selected blocks the decode kernel copies and attends at a time, one
# chunk of its inner loop (timings of the alternatives on a v5e: PERF.md,
# PR 30).
_BLOCKS_PER_CHUNK = 64


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


def _block_ranks(q, ckeys, pos, n_blocks: int, spec: SparseSpec):
    """What :func:`select_blocks` picks by: rank [B, Hkv, Q, NB] float32,
    ``_BIG`` for a block the query must read, the block's score for one it
    may, ``-_BIG`` for one past it; and dense [B, Q, 1], whether the
    query's context is no longer than ``dense_len``."""
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
    return jnp.where((blk <= p_ // spec.block)[:, None], rank, -_BIG), dense


def select_blocks(q, ckeys, pos, n_blocks: int, spec: SparseSpec):
    """q [B, Hkv, G, Q, hd] at positions ``pos`` [B, Q]; ckeys
    [B, Hkv, W, hd]. Returns (idx [B, Hkv, Q, n] int32 block indices, ok
    [B, Hkv, Q, n] bool) with n = ``spec.n_select``: the blocks to read,
    the forced ones first, then by score, of equal scores the lower block
    first; a slot that is not ``ok`` is padding."""
    rank, dense = _block_ranks(q, ckeys, pos, n_blocks, spec)
    n = spec.n_select(n_blocks * spec.block)
    # Every (row, head, query) a row of ONE leading dimension: the TPU's
    # compiler then lays the rows along the lanes and sorts each down the
    # sublanes. Handed [B, Hkv, 1, NB], a decode step's shape, it sorts
    # along the lanes, a row to a tile, several times as long (PERF.md,
    # PR 35).
    vals, idx = lax.top_k(rank.reshape(-1, n_blocks), n)
    vals, idx = (a.reshape(*rank.shape[:-1], n) for a in (vals, idx))
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


def decode_implementation(spec: SparseSpec, head_dim: int) -> str:
    """What :func:`sparse_decode_attention` runs when it is left to choose:
    ``"pallas"`` where the kernel compiles (a TPU, lane-aligned tiles),
    else ``"xla"``."""
    return "xla" if paged_tile_unsupported(spec.block, head_dim) else "pallas"


def sparse_decode_attention(q, pool_k, pool_v, layer: int, table, idx, ok,
                            pos, spec: SparseSpec, *,
                            implementation: str | None = None,
                            interpret: bool = False):
    """One query per row over the blocks its selection names, read out of
    the block pool. q [B, Hkv, G, hd]; pool_k, pool_v the WHOLE pools
    [La, N, Hkv, Bs, hd] with Bs the selection's block and ``layer`` the
    (static) sparse layer; table [B, MB] (entries >= N are unallocated
    sentinels and clamp); idx, ok [B, Hkv, n] as :func:`select_blocks`
    returns them (``ok`` a prefix); pos [B]. → [B, Hkv, G, hd] in q's dtype.

    ``implementation``: None (:func:`decode_implementation`), "pallas" or
    "xla". An explicit "pallas" that cannot be compiled for this backend or
    shape raises (``interpret=True`` runs it in the interpreter anywhere).
    The kernel reads ``ok.sum`` tiles a (row, KV head) in place; the XLA
    path gathers all n into a copy and masks."""
    b, hkv, _g, hd = q.shape
    if implementation is None:
        implementation = decode_implementation(spec, hd)
    elif implementation == "pallas" and not interpret:
        if why := paged_tile_unsupported(spec.block, hd):
            raise ValueError(
                f"sparse_decode_attention(implementation='pallas'): {why}")
    if implementation == "xla":
        phys = jnp.take_along_axis(
            jnp.broadcast_to(table[:, None, :], (b, hkv, table.shape[1])),
            idx, axis=2)
        heads = jnp.arange(hkv)[None, :, None]
        return attend_selected(q, pool_k[layer, phys, heads],
                               pool_v[layer, phys, heads], idx, ok, pos,
                               spec)
    if implementation != "pallas":
        raise ValueError(f"unknown implementation {implementation!r}")
    # ``ok`` is a prefix, so a count says which slots are real; of those
    # only the block that holds ``pos`` is cut short (select_blocks drops
    # every block past it), so its slot and the lanes it keeps say the rest.
    count = ok.sum(axis=-1, dtype=jnp.int32)
    cut = jnp.argmax(idx == (pos // spec.block)[:, None, None], axis=-1)
    return _attend_pool_pallas(
        q, pool_k, pool_v, layer, table, idx, count, cut.astype(jnp.int32),
        (pos % spec.block).astype(jnp.int32), interpret=interpret)


def _attend_pool_pallas(q, pool_k, pool_v, layer: int, table, idx, count,
                        cut, keep, *,
                        blocks_per_chunk: int = _BLOCKS_PER_CHUNK,
                        interpret: bool = False):
    """The kernel of :func:`sparse_decode_attention`. table [B, MB] and
    idx [B, Hkv, n] (a slot's physical block is ``table[row, idx]``, looked
    up by the kernel's scalar core: as an XLA gather of B·Hkv·n scalars it
    took half as long as the kernel itself); count [B, Hkv] how many slots
    are read; cut [B, Hkv] the slot whose block keeps lanes ``<= keep[b]``
    only (keep [B]). All five are scalar-prefetched.

    Grid (B, Hkv), one (row, KV head) a grid step, in order. The pools stay
    in HBM; a step's inner loop takes a chunk of ``blocks_per_chunk`` slots
    at a time: one async copy per (block, head) tile [Bs, hd] (contiguous:
    the pool is head-major inside a block) into one of two VMEM buffers,
    the next chunk's copies (the next grid step's first chunk, at a step's
    last) started before this chunk's are waited for. A chunk is copied whole,
    so up to ``blocks_per_chunk - 1`` tiles past a row's count are read and
    masked; chunks past it are neither copied nor computed. Scores, the
    running maximum, the normaliser and the accumulator are float32; K, V
    and the probabilities enter the MXU at the pool's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hkv, g, hd = q.shape
    n_pool, bs = pool_k.shape[1], pool_k.shape[3]
    mb = table.shape[1]
    p = min(blocks_per_chunk, idx.shape[-1])
    idx = jnp.pad(idx, ((0, 0), (0, 0), (0, -idx.shape[-1] % p)))
    n = idx.shape[-1]
    steps = b * hkv
    sm_scale = hd ** -0.5

    def kernel(table_ref, idx_ref, count_ref, cut_ref, keep_ref, q_ref,
               k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, nxt_ref):
        # nxt_ref[0]: the buffer the next chunk to compute lands in;
        # nxt_ref[1]: whether the grid step before this one has started
        # this step's first chunk.
        row, head = pl.program_id(0), pl.program_id(1)
        i = row * hkv + head
        n_read, cut_slot, keep_lanes = count_ref[i], cut_ref[i], keep_ref[row]
        chunks = pl.cdiv(n_read, p)

        def start(step, chunk, buf):
            """Start the 2p copies of grid step ``step``'s chunk."""
            r, h = lax.div(step, hkv), lax.rem(step, hkv)
            for j in range(p):
                # An unallocated entry (>= N) clamps; it lies past the count.
                blk = jnp.minimum(
                    table_ref[r * mb + idx_ref[step * n + chunk * p + j]],
                    n_pool - 1)
                for hbm, vmem, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    pltpu.make_async_copy(hbm.at[layer, blk, h],
                                          vmem.at[buf, j],
                                          sems.at[s, buf]).start()

        def wait(vmem, s, buf):
            # A wait needs the copy's shape only: p tiles on one semaphore.
            for j in range(p):
                pltpu.make_async_copy(k_hbm.at[layer, 0, 0],
                                      vmem.at[buf, j],
                                      sems.at[s, buf]).wait()

        @pl.when(i == 0)
        def _first_step():
            nxt_ref[0] = 0
            nxt_ref[1] = 0

        @pl.when((chunks > 0) & (nxt_ref[1] == 0))
        def _own_first_chunk():
            start(i, 0, nxt_ref[0])

        nxt_ref[1] = 0
        qh = q_ref[...]
        tok = lax.broadcasted_iota(jnp.int32, (1, p * bs), 1)

        def body(c, carry):
            m, l, acc = carry
            buf = nxt_ref[0]
            other = 1 - buf

            @pl.when(c + 1 < chunks)
            def _next_chunk():
                start(i, c + 1, other)

            after = jnp.minimum(i + 1, steps - 1)

            @pl.when((c + 1 == chunks) & (i + 1 < steps)
                     & (count_ref[after] > 0))
            def _next_steps_first_chunk():
                start(after, 0, other)
                nxt_ref[1] = 1

            nxt_ref[0] = other
            wait(k_buf, 0, buf)
            k = k_buf[buf].reshape(p * bs, hd)
            s = lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            slot = c * p + tok // bs
            seen = (slot < n_read) & (
                (slot != cut_slot) | (tok % bs <= keep_lanes))
            s = jnp.where(seen, s * sm_scale, -_BIG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            e = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            fade = jnp.exp(m - m_new)
            wait(v_buf, 1, buf)
            v = v_buf[buf].reshape(p * bs, hd)
            acc = acc * fade + jnp.dot(e.astype(v.dtype), v,
                                       preferred_element_type=jnp.float32)
            return m_new, l * fade + e.sum(axis=-1, keepdims=True), acc

        _, l, acc = lax.fori_loop(
            0, chunks, body,
            (jnp.full((g, 1), -_BIG, jnp.float32),
             jnp.zeros((g, 1), jnp.float32),
             jnp.zeros((g, hd), jnp.float32)))
        # Nothing read (count 0): 0, as _softmax_pv gives.
        o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    q_spec = pl.BlockSpec((None, None, g, hd),
                          lambda row, head, *_: (row, head, 0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, hkv),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, p, bs, hd), pool_k.dtype),
                pltpu.VMEM((2, p, bs, hd), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="sparse_decode_attention",
    )(table.reshape(-1), idx.reshape(-1), count.reshape(-1), cut.reshape(-1),
      keep, q, pool_k, pool_v)


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
