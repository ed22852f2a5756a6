"""Flash attention for TPU: pallas MXU kernel or blockwise XLA.

Three implementations behind one API:

- ``"pallas"``: the tiled TPU flash kernel (fused forward AND backward,
  causal block skipping — blocks above the diagonal are never computed, so
  attention flops halve at long sequence). This is the long-sequence
  training path: at seq1024+ the XLA single-block path pays the full
  [T, S] score matmuls in fwd, bwd, and the flash recompute, which is
  where the deep model's MFU went at realistic context (VERDICT r3 #1).
  GQA folds the query-head group into the batch so keys/values are never
  materialized at H_q width.
- ``"xla"`` (and the auto default off-TPU): blockwise online softmax over
  kv blocks with ``lax.scan``; backward recomputes p from the saved
  logsumexp. At ``block_k == T`` the scan collapses to a single fused
  block — the measured-fastest short-sequence configuration (27.3k vs
  23.8k tok/s at block_k=128 on the shallow flagship).
- ``"plain"``: materialized [T, S] scores — fastest when T is small and
  O(T·S) memory is irrelevant.

History: round 2's hand-written pallas kernel lost catastrophically inside
the full flagship train step (1.2k vs 27.3k tok/s; git history has it) —
it had no causal skipping and a recompute-everything backward. Round 4's
rematch with a block-skipping fused-backward kernel wins at depth and
realistic sequence length: +6-9 MFU points on flagship-deep at seq1024.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from kubeflow_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_TENSOR
from kubeflow_tpu.utils.jaxenv import not_tpu

_NEG_INF = -1e30
# Default kv block widths when the caller leaves block_k=None: the XLA
# blockwise path takes DEFAULT_BLOCK_K (callers with known-static sequence
# lengths should pass block_k == seq_len — single block, measured fastest
# on v5e; 2048 keeps memory O(T·2048) for long sequences), the TPU kernels
# take DEFAULT_KERNEL_BLOCK_K (1024-wide tiles measured faster than 2048
# at seq≥2048, and VMEM-safe).
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 2048
DEFAULT_KERNEL_BLOCK_K = 1024


def _causal_mask(q_start, k_start, bq, bk):
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos >= k_pos


# ---------------------------------------------------------------------------
# Blockwise XLA path (CPU fallback + backward recomputation)
# ---------------------------------------------------------------------------


def _kv_blocks(x, nk, block_k):
    # [BKV, S, ...] -> iteration-major [nk, BKV, block_k, ...]
    bkv = x.shape[0]
    return x.reshape(bkv, nk, block_k, *x.shape[2:]).swapaxes(0, 1)


def _flash_fwd_xla(q, k, v, kvm, *, causal, scale, block_k):
    """Same online-softmax accumulation as the kernel, as a scan over kv
    blocks. q: [BKV, G, T, D]; k,v: [BKV, S, D]; kvm: [BKV, S, 1]."""
    bkv, g, t, d = q.shape
    s_len = k.shape[1]
    block_k = min(block_k, s_len)
    if s_len % block_k:
        block_k = s_len  # odd lengths: single block, still O(T·block) mem
    nk = s_len // block_k
    q32 = q.astype(jnp.float32)

    def step(carry, blk):
        m, l, acc = carry
        k_b, v_b, kvm_b, j = blk
        s = jnp.einsum("bgqd,bkd->bgqk", q32, k_b,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = _causal_mask(0, j * block_k, t, block_k)
            s = jnp.where(mask[None, None], s, _NEG_INF)
        s = jnp.where(kvm_b[..., 0][:, None, None, :] > 0, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bgqk,bkd->bgqd", p, v_b)
        return (m_new, l, acc), None

    init = (
        jnp.full((bkv, g, t, 1), _NEG_INF, jnp.float32),
        jnp.zeros((bkv, g, t, 1), jnp.float32),
        jnp.zeros((bkv, g, t, d), jnp.float32),
    )
    (m, l, acc), _ = lax.scan(
        step, init,
        (_kv_blocks(k.astype(jnp.float32), nk, block_k),
         _kv_blocks(v.astype(jnp.float32), nk, block_k),
         _kv_blocks(kvm, nk, block_k),
         jnp.arange(nk)),
    )
    # Rows with every key masked never saw a finite score (m stayed at
    # _NEG_INF, p degenerated to exp(0)=1 per key): return zeros, not mean(V).
    valid = m > _NEG_INF / 2
    out = jnp.where(valid, acc / l, 0.0).astype(q.dtype)
    lse = jnp.where(valid, m + jnp.log(l), _NEG_INF)
    return out, lse


def _flash_bwd_xla(q, k, v, kvm, out, lse, g_out, *, causal, scale, block_k):
    """Flash backward: recompute p blockwise from lse; scan over kv blocks."""
    bkv, g, t, d = q.shape
    s_len = k.shape[1]
    block_k = min(block_k, s_len)
    if s_len % block_k:
        block_k = s_len
    nk = s_len // block_k
    q32, g32 = q.astype(jnp.float32), g_out.astype(jnp.float32)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1, keepdims=True)

    def step(dq, blk):
        k_b, v_b, kvm_b, j = blk
        s = jnp.einsum("bgqd,bkd->bgqk", q32, k_b,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = _causal_mask(0, j * block_k, t, block_k)
            s = jnp.where(mask[None, None], s, _NEG_INF)
        s = jnp.where(kvm_b[..., 0][:, None, None, :] > 0, s, _NEG_INF)
        # All-masked rows carry lse=_NEG_INF; exp(s-lse) would degenerate to
        # 1 per key — their p (and so dk/dv/dq contributions) must be zero.
        p = jnp.where(lse > _NEG_INF / 2, jnp.exp(s - lse), 0.0)
        dp = jnp.einsum("bgqd,bkd->bgqk", g32, v_b)
        ds = p * (dp - delta) * scale
        dq = dq + jnp.einsum("bgqk,bkd->bgqd", ds, k_b)
        dk_b = jnp.einsum("bgqk,bgqd->bkd", ds, q32)
        dv_b = jnp.einsum("bgqk,bgqd->bkd", p, g32)
        return dq, (dk_b, dv_b)

    dq, (dk_blocks, dv_blocks) = lax.scan(
        step, jnp.zeros((bkv, g, t, d), jnp.float32),
        (_kv_blocks(k.astype(jnp.float32), nk, block_k),
         _kv_blocks(v.astype(jnp.float32), nk, block_k),
         _kv_blocks(kvm, nk, block_k),
         jnp.arange(nk)),
    )
    dk = dk_blocks.swapaxes(0, 1).reshape(bkv, s_len, d)
    dv = dv_blocks.swapaxes(0, 1).reshape(bkv, s_len, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel path (fused bwd + causal block skipping)
# ---------------------------------------------------------------------------


def _kernel_unsupported(q, k, kv_mask, mesh=None) -> str | None:
    """Why the tiled TPU kernels cannot take this call (None = they can):
    they want a TPU, no padding mask, lane-width head_dim, MXU-aligned
    sequence tiles and, on a mesh, batch and KV heads that divide over
    the axes :func:`_shard_kernel` splits them on. Inside another
    ``shard_map`` (a pipeline stage) they are fenced off: the nested
    wrap that would take the axes still automatic has never run on a
    chip."""
    if why := not_tpu():
        return why
    if jax.sharding.get_abstract_mesh().manual_axes:
        return ("inside a shard_map (a pipeline stage) the TPU kernels "
                "are not wired up; the XLA path runs there")
    if kv_mask is not None:
        return "the TPU kernels take no kv_mask"
    b, t, _hq, d = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    if d % 128 or t % 128 or s_len % 128:
        return (f"head_dim {d}, T {t} and S {s_len} must be multiples "
                "of 128")
    if mesh is not None and mesh.size > 1:
        batch_ways = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
        head_ways = mesh.shape[AXIS_TENSOR]
        if b % batch_ways or hkv % head_ways:
            return (f"batch {b} / kv heads {hkv} do not divide over "
                    f"data*fsdp={batch_ways} / tensor={head_ways}")
    return None


def _shard_kernel(kernel, mesh):
    """Mosaic kernels cannot be partitioned by GSPMD: on a multi-device
    mesh run ``kernel(q, k, v)`` per shard under ``shard_map`` — batch
    over data*fsdp, heads over tensor (attention is independent per
    batch row and per KV-head group, so no collective is needed), every
    other axis replicated."""
    from jax.sharding import PartitionSpec as P

    from kubeflow_tpu.parallel.collectives import shard_map

    spec = P((AXIS_DATA, AXIS_FSDP), None, AXIS_TENSOR, None)
    return shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)


def _pallas_flash(q, k, v, *, causal, scale, block):
    """q: [B, T, Hq, D]; k, v: [B, S, Hkv, D] → [B, T, Hq, D] via the
    pallas TPU flash kernel (jax.experimental.pallas.ops.tpu). The kernel
    is MHA; GQA folds the query-head group into the kernel's head axis
    ([B·Hkv, G, T, D]) with K/V broadcast across the group (XLA
    materializes the broadcast for the kernel call, but the gradient sums
    straight back to the [B, S, Hkv, D] layout). Block width 1024 measured
    fastest at seq1024/2048 on v5e (vs 512: +0.5-0.9 MFU pt; vs 256:
    -4.3 pts)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention as _kernel,
    )

    b, t, hq, d = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    # [B, T, Hq, D] -> [B·Hkv, G, T, D]; K/V -> [B·Hkv, 1, S, D] broadcast
    # over the group axis (the kernel's "heads" dim).
    qf = (q.transpose(0, 2, 1, 3)
          .reshape(b, hkv, group, t, d)
          .reshape(b * hkv, group, t, d))
    kf = jnp.broadcast_to(
        k.transpose(0, 2, 1, 3).reshape(b * hkv, 1, s_len, d),
        (b * hkv, group, s_len, d))
    vf = jnp.broadcast_to(
        v.transpose(0, 2, 1, 3).reshape(b * hkv, 1, s_len, d),
        (b * hkv, group, s_len, d))
    bq = min(block, t)
    bk = min(block, s_len)
    sizes = BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
        block_q_dq=bq,
    )
    out = _kernel(qf, kf, vf, causal=causal, sm_scale=scale,
                  block_sizes=sizes)
    return (out.reshape(b, hkv, group, t, d)
            .reshape(b, hq, t, d)
            .transpose(0, 2, 1, 3))


@functools.lru_cache(maxsize=32)
def _splash_kernel(group: int, t: int, s_len: int, causal: bool,
                   block: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as ml,
    )

    if causal:
        heads = [ml.CausalMask((t, s_len)) for _ in range(group)]
    else:
        heads = [ml.FullMask((t, s_len)) for _ in range(group)]
    blk = min(block, t, s_len)
    sizes = sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk,
    )
    # The kernel object carries its mask tables as arrays. This cache
    # outlives the trace that first asks for it (jit, scan, shard_map —
    # where jnp.array yields that trace's tracers), so build them as
    # concrete values; the next trace would otherwise be handed tracers
    # of a finished one (seen on the chip: UnexpectedTracerError on the
    # second mesh a process trained on).
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            mask=ml.MultiHeadMask(heads), block_sizes=sizes,
            residual_checkpoint_name="attn_res",
        )


def _splash_flash(q, k, v, *, causal, scale, block):
    """GQA-native splash attention: one kernel per kv head with the query
    group riding the kernel's head axis — K/V are never materialized at
    H_q width (the flash-kernel path broadcasts them ``group``×). The
    kernel checkpoints its residuals under the name ``"attn_res"`` so the
    "llm_res" remat policy can keep them across the backward instead of
    re-running the forward kernel."""
    b, t, hq, d = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    kernel = _splash_kernel(group, t, s_len, causal, block)
    # Splash takes pre-scaled queries ([B, Hkv, G, T, D] vs K/V
    # [B, Hkv, S, D]); vmap over batch then kv-head.
    qf = ((q * scale).astype(q.dtype)
          .transpose(0, 2, 1, 3)
          .reshape(b, hkv, group, t, d))
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kernel))(qf, kf, vf)  # [B, Hkv, G, T, D]
    return (out.reshape(b, hq, t, d).transpose(0, 2, 1, 3))


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------


def _plain_attention(q, k, v, kvm, *, causal, scale):
    """Reference path: materialize the [G,T,S] score matrix. On TPU this is
    often the fastest choice at moderate T — one fused softmax over a single
    large MXU matmul pair beats a sequential scan of small blocks — at the
    cost of O(T·S) activation memory. q: [BKV, G, T, D]; k,v: [BKV, S, D]."""
    s = jnp.einsum("bgqd,bkd->bgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    t, s_len = q.shape[2], k.shape[1]
    if causal:
        mask = _causal_mask(0, 0, t, s_len)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    s = jnp.where(kvm[..., 0][:, None, None, :] > 0, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    valid = m > _NEG_INF / 2  # all-masked rows → zeros, matching flash
    p = jnp.exp(s - jnp.where(valid, m, 0.0))
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bgqk,bkd->bgqd", p, v.astype(jnp.float32))
    out = jnp.where(valid, acc / jnp.where(l == 0, 1.0, l), 0.0)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, kvm, causal, scale, block_q, block_k):
    out, _ = _flash_fwd_xla(q, k, v, kvm, causal=causal, scale=scale,
                            block_k=block_k)
    return out


def _flash_vjp_fwd(q, k, v, kvm, causal, scale, block_q, block_k):
    out, lse = _flash_fwd_xla(q, k, v, kvm, causal=causal, scale=scale,
                              block_k=block_k)
    return out, (q, k, v, kvm, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, kvm, out, lse = res
    dq, dk, dv = _flash_bwd_xla(q, k, v, kvm, out, lse, g, causal=causal,
                                scale=scale, block_k=block_k)
    return dq, dk, dv, jnp.zeros_like(kvm)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Paged block-table decode attention (models/decode.py's fused read path)
# ---------------------------------------------------------------------------
#
# The paged KV layout stores K/V in a pool of fixed-size blocks; slot
# ``b``'s virtual position ``p`` lives at block ``table[b, p // Bs]``,
# offset ``p % Bs``. The reference read path gathers the whole virtual
# row ``[B, MB*Bs, Hkv, hd]`` per layer per decode step before dense
# attention — at serving shapes that materialization IS the decode
# bandwidth bill. The fused paths below walk the table instead and
# compute span attention one block at a time with an online softmax, so
# the dense view never exists:
#
# - ``"xla"``: a ``lax.scan`` over table columns (any backend) — each
#   step touches one ``[B, Bs, Hkv, hd]`` block.
# - ``"pallas"``: the TPU kernel. The block table and per-row positions
#   ride scalar prefetch so the index_map DMAs exactly the physical
#   block each grid step needs; int8 pools are dequantized in-register
#   (scale broadcast over the lane dim) between the DMA and the MXU.
#
# Pools may be quantized: ``{"q": int8 [N, Bs, Hkv, hd], "scale": f32
# [N, Bs, Hkv]}`` with one abs-max scale per (position, kv head).
# Numerics: scores/softmax/accumulation in f32 (an online softmax is not
# bitwise-identical to the one-shot reference, which is why
# models/decode.py keeps the gather path as the pinned-parity default).


def _kv_payload(pool):
    """The payload array of a (possibly quantized) block pool."""
    return pool["q"] if isinstance(pool, dict) else pool


def _read_block(pool, blk):
    """Gather ONE physical block per row ([B] ids → [B, Bs, Hkv, hd] f32),
    dequantizing int8 payloads against their per-position scales."""
    if isinstance(pool, dict):
        return (pool["q"][blk].astype(jnp.float32)
                * pool["scale"][blk][..., None])
    return pool[blk].astype(jnp.float32)


def _paged_decode_xla(qg, k_pool, v_pool, table, pos, sm_scale):
    """Blockwise online-softmax walk of the table. qg: [B, Hkv, G, hd];
    pools: [N, Bs, Hkv, hd] (or quantized dicts); table: [B, MB]; pos:
    [B] (row attends virtual positions <= pos). Returns [B, Hkv, G, hd]
    f32 — no ``[B, MB*Bs]`` view is ever built."""
    n, bs = _kv_payload(k_pool).shape[0], _kv_payload(k_pool).shape[1]
    b, hkv, g, hd = qg.shape
    mb = table.shape[1]
    q32 = qg.astype(jnp.float32)

    def step(carry, j):
        m, l, acc = carry
        # Sentinel entries (>= N, the unallocated marker) clamp to the
        # last block; the junk they surface sits past ``pos`` where the
        # span mask already excludes it.
        blk = jnp.clip(table[:, j], 0, n - 1)
        k_b = _read_block(k_pool, blk)
        v_b = _read_block(v_pool, blk)
        s = jnp.einsum("bkgd,bskd->bkgs", q32, k_b,
                       preferred_element_type=jnp.float32) * sm_scale
        span = j * bs + jnp.arange(bs)[None, :]
        s = jnp.where((span <= pos[:, None])[:, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bkgs,bskd->bkgd", p, v_b)
        return (m_new, l, acc), None

    init = (
        jnp.full((b, hkv, g, 1), _NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, g, 1), jnp.float32),
        jnp.zeros((b, hkv, g, hd), jnp.float32),
    )
    (m, l, acc), _ = lax.scan(step, init, jnp.arange(mb))
    ok = m > _NEG_INF / 2  # pos >= 0 keeps slot 0 live, but stay defensive
    return jnp.where(ok, acc / jnp.where(l == 0.0, 1.0, l), 0.0)


def _paged_decode_pallas(qg, k_pool, v_pool, table, pos, sm_scale,
                         interpret=False):
    """TPU kernel twin of :func:`_paged_decode_xla`. Grid is
    ``(B, Hkv, MB)`` with the table column innermost; the scalar-prefetched
    table drives each step's K/V DMA (the gather never exists, not even
    blockwise on host), and int8 tiles are dequantized in-register."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quant = isinstance(k_pool, dict)
    kq = _kv_payload(k_pool)
    n, bs, hkv, hd = kq.shape
    b, _, g, _ = qg.shape
    mb = table.shape[1]
    # Head-major pools: one (block, head) tile [Bs, hd] is a contiguous
    # DMA. Scales get a trailing singleton so their tile is 2D.
    kt = kq.transpose(0, 2, 1, 3)
    vt = _kv_payload(v_pool).transpose(0, 2, 1, 3)
    operands = [kt, vt]
    if quant:
        operands += [k_pool["scale"].transpose(0, 2, 1)[..., None],
                     v_pool["scale"].transpose(0, 2, 1)[..., None]]

    def kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
        else:
            o_ref, m_ref, l_ref, acc_ref = rest
        row = pl.program_id(0)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        if quant:  # in-register dequant: [Bs, 1] scale over the lane dim
            k = k * ks_ref[:]
            v = v * vs_ref[:]
        s = jnp.dot(q_ref[:].astype(jnp.float32), k.T,
                    preferred_element_type=jnp.float32) * sm_scale
        span = j * bs + lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(span <= pos_ref[row], s, _NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

        @pl.when(j == mb - 1)
        def _flush():
            l = l_ref[:]
            ok = m_ref[:] > _NEG_INF / 2
            o_ref[:] = jnp.where(
                ok, acc_ref[:] / jnp.where(l == 0.0, 1.0, l), 0.0)

    def _blk(tbl, _pos, row, j):
        # Sentinel entries clamp like the XLA path; the span mask hides
        # whatever the clamped DMA brings in.
        return jnp.minimum(tbl[row, j], n - 1)

    in_specs = [
        pl.BlockSpec((None, None, g, hd),
                     lambda row, h, j, tbl, pos: (row, h, 0, 0)),
        pl.BlockSpec((None, None, bs, hd),
                     lambda row, h, j, tbl, pos: (_blk(tbl, pos, row, j),
                                                  h, 0, 0)),
        pl.BlockSpec((None, None, bs, hd),
                     lambda row, h, j, tbl, pos: (_blk(tbl, pos, row, j),
                                                  h, 0, 0)),
    ]
    if quant:
        in_specs += [
            pl.BlockSpec((None, None, bs, 1),
                         lambda row, h, j, tbl, pos: (_blk(tbl, pos, row, j),
                                                      h, 0, 0)),
            pl.BlockSpec((None, None, bs, 1),
                         lambda row, h, j, tbl, pos: (_blk(tbl, pos, row, j),
                                                      h, 0, 0)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, g, hd),
                               lambda row, h, j, tbl, pos: (row, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(table.astype(jnp.int32), pos.astype(jnp.int32), qg, *operands)


def paged_tile_unsupported(block_size: int, head_dim: int) -> str | None:
    """Why a compiled (non-interpret) kernel cannot read (block, head)
    tiles ``[block_size, head_dim]`` out of a block pool (None = it can):
    it wants a TPU and lane/sublane-aligned tiles. The one rule of every
    block-table kernel (here and ``ops/sparse_attention.py``)."""
    if why := not_tpu():
        return why
    if head_dim % 128 or block_size % 8:
        return (f"head_dim {head_dim} must be a multiple of 128 and the "
                f"block size {block_size} a multiple of 8")
    return None


def _paged_kernel_unsupported(k_pool) -> str | None:
    """:func:`paged_tile_unsupported` of a ``[N, Bs, Hkv, hd]`` pool."""
    _n, bs, _hkv, hd = _kv_payload(k_pool).shape
    return paged_tile_unsupported(bs, hd)


def _paged_decode_local(qg, k_pool, v_pool, table, pos, sm_scale,
                        implementation, interpret):
    """Single-shard dispatch of the block walk (also the per-shard body
    of the mesh twin): qg [B, Hkv, G, hd] against [N, Bs, Hkv, hd]
    pools."""
    if implementation is None:
        implementation = ("xla" if _paged_kernel_unsupported(k_pool)
                          else "pallas")
    elif implementation == "pallas" and not interpret:
        why = _paged_kernel_unsupported(k_pool)
        if why:
            raise ValueError(
                f"paged_decode_attention(implementation='pallas'): {why}")
    if implementation == "pallas":
        return _paged_decode_pallas(qg, k_pool, v_pool, table, pos,
                                    sm_scale, interpret=interpret)
    if implementation == "xla":
        return _paged_decode_xla(qg, k_pool, v_pool, table, pos, sm_scale)
    raise ValueError(f"unknown implementation {implementation!r}")


def _pool_head_specs(pool, axis: str, lead: int = 2):
    """PartitionSpec pytree sharding a block pool on its KV-head dim
    (``lead`` dims before it: [N, Bs] here, [L, N, Bs] for stacked
    pools). Quantized pools shard codes AND scales by the same axis —
    they ride the same block ids, so the split is one move."""
    from jax.sharding import PartitionSpec as P

    head = [None] * lead + [axis]
    if isinstance(pool, dict):
        return {"q": P(*head, None), "scale": P(*head)}
    return P(*head, None)


def _shard_heads(mesh, axis: str, n_kv_heads: int) -> int:
    """Validate the KV-head axis divides over ``axis`` and return the
    shard count (1 = mesh absent or axis unsplit)."""
    if mesh is None:
        return 1
    shards = int(mesh.shape.get(axis, 1))
    if shards > 1 and n_kv_heads % shards:
        raise ValueError(
            f"{n_kv_heads} kv heads not divisible by {shards} shards "
            f"on mesh axis {axis!r}")
    return shards


def paged_decode_attention(q, k_pool, v_pool, table, pos, *,
                           n_kv_heads: int, scale: float | None = None,
                           implementation: str | None = None,
                           interpret: bool = False,
                           mesh=None, axis: str = "tensor"):
    """Fused single-token attention over a paged KV pool.

    q: [B, Hq, hd] (one decode token per row, already rotary-embedded);
    k_pool/v_pool: [N, Bs, Hkv, hd] block pools, or quantized dicts
    ``{"q": int8, "scale": f32 [N, Bs, Hkv]}``; table: [B, MB] block
    table (entries >= N are unallocated sentinels); pos: [B] — row ``b``
    attends virtual positions ``<= pos[b]``. Returns [B, Hq, hd] f32.

    ``implementation``: None (auto: pallas on TPU for supported shapes,
    else xla), "pallas", or "xla". An explicit "pallas" that cannot be
    compiled for this backend or shape raises (``interpret=True`` runs
    it in the interpreter anywhere). Both walk the block table with an
    online softmax — the gathered ``[B, MB*Bs, Hkv, hd]`` view is never
    materialized, which is the point.

    ``mesh`` (with ``axis`` sized > 1) selects the tensor-parallel twin:
    the pool is sharded over the KV-head dim and each shard walks the
    SAME block table over its local heads under ``shard_map``. The
    online-softmax state (m/l/acc) is per-head, so the walk needs no
    cross-shard collective at all — the output stays head-sharded for
    the row-parallel ``wo`` matmul, whose psum is the block's one
    reduction. Per-shard results are bitwise-equal to the single-device
    kernel's corresponding head slices."""
    b, hq, hd = q.shape
    if hq % n_kv_heads:
        raise ValueError(
            f"query heads {hq} not a multiple of kv heads {n_kv_heads}")
    group = hq // n_kv_heads
    sm_scale = (hd ** -0.5) if scale is None else scale
    qg = q.reshape(b, n_kv_heads, group, hd)
    if _shard_heads(mesh, axis, n_kv_heads) > 1:
        from jax.sharding import PartitionSpec as P

        from kubeflow_tpu.parallel.collectives import shard_map

        def _local(qg_l, k_l, v_l, tbl, pos_l):
            return _paged_decode_local(qg_l, k_l, v_l, tbl, pos_l,
                                       sm_scale, implementation, interpret)

        out = shard_map(
            _local, mesh=mesh,
            in_specs=(P(None, axis, None, None),
                      _pool_head_specs(k_pool, axis),
                      _pool_head_specs(v_pool, axis), P(), P()),
            out_specs=P(None, axis, None, None),
            axis_names=frozenset({axis}),
        )(qg, k_pool, v_pool, table, pos)
    else:
        out = _paged_decode_local(qg, k_pool, v_pool, table, pos, sm_scale,
                                  implementation, interpret)
    return out.reshape(b, hq, hd)


# ---------------------------------------------------------------------------
# Length-bounded decode attention over the dense store (models/decode.py's
# single-token read where no block table is)
# ---------------------------------------------------------------------------
#
# The dense cache is ``[cache layers, B, T, Hkv, hd]``: a row's positions
# lie one after another, all KV heads of a position together. The XLA read
# slices layer ``li`` out whole and masks: every row's T positions are read
# whatever the row holds. The kernel below reads positions
# ``0 .. length[row] - 1`` of each row out of the WHOLE store where it lies
# (the layer index is a scalar it is handed, so no layer is sliced out and
# nothing of the store's size is made), a chunk of positions at a time.

# Positions a chunk of the kernel's inner loop copies and attends (timings
# of the alternatives on a v5e: PERF.md, PR 37).
_DENSE_CHUNK = 128


def _dense_kernel_unsupported(head_dim: int, dtype) -> str | None:
    """Why the compiled kernel cannot read a dense store of this head size
    and dtype (None = it can)."""
    if why := not_tpu():
        return why
    if head_dim % 128 or not jnp.issubdtype(dtype, jnp.floating):
        return (f"head_dim {head_dim} must be a multiple of 128 and the "
                f"store ({jnp.dtype(dtype).name}) floating-point")
    return None


def dense_decode_implementation(head_dim: int, dtype) -> str:
    """What :func:`dense_decode_attention` runs when it is left to choose:
    ``"pallas"`` where the kernel compiles (a TPU, lane-aligned heads, a
    floating-point store), else ``"xla"``."""
    return "xla" if _dense_kernel_unsupported(head_dim, dtype) else "pallas"


def _dense_decode_xla(qg, k_store, v_store, li, lengths):
    """The layer's rows sliced out whole and masked: qg [B, Hkv, G, hd]
    → [B, Hkv, G, hd] in q's dtype."""
    k = lax.dynamic_index_in_dim(k_store, li, 0, keepdims=False)
    v = lax.dynamic_index_in_dim(v_store, li, 0, keepdims=False)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * qg.shape[-1] ** -0.5
    seen = (jnp.arange(k.shape[1])[None] < lengths[:, None])[:, None, None]
    top = jnp.max(jnp.where(seen, scores, _NEG_INF), axis=-1, keepdims=True)
    e = jnp.where(seen, jnp.exp(scores - top), 0.0)
    p = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bkgt,btkd->bkgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(qg.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _dense_decode_pallas(q, k_store, v_store, li, lengths, *,
                         chunk: int = _DENSE_CHUNK, interpret: bool = False):
    """The kernel of :func:`dense_decode_attention`. q [B, Hq, hd]; the
    stores whole, in HBM; ``li`` (traced) and ``lengths`` [B] are
    scalar-prefetched. Jitted on its own so that the kernel is traced once
    a process and shape: a decoder jits a decode step into every admission
    shape it serves, two dozen of them, and each would trace it again.

    Grid (B,), one row a grid step, in order. A step's inner loop takes
    ``chunk`` positions at a time: ``[chunk, Hkv, hd]`` of K and of V, one
    contiguous run of the store each, one async copy each into one of two
    VMEM buffers, the next chunk's copies (the next row's first chunk, at a
    row's last) started before this chunk's are waited for. A chunk is
    copied whole, so up to ``chunk - 1`` positions past a row's length are
    read and masked; chunks past it are neither copied nor computed. A row
    of length 0 copies and computes nothing and writes zeros.

    All heads of a chunk are ONE product a side: scores
    ``[Hq, hd] · [chunk·Hkv, hd]ᵀ`` with the columns of the other KV heads
    masked, values ``[Hq, chunk·Hkv] · [chunk·Hkv, hd]``. A position's
    heads lie along the sublanes of one tile, so a head's own ``[chunk,
    hd]`` would be a strided gather; as it is the MXU sees each K and V
    element once, as a product a head would, and the scores of a chunk
    are ``Hq·Hkv·chunk`` floats. Scores, running maximum, normaliser and
    accumulator are float32; K, V and the probabilities enter the MXU at
    the store's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, hd = q.shape
    t, hkv = k_store.shape[2], k_store.shape[3]
    group = hq // hkv
    tc = min(chunk, t)
    sm_scale = hd ** -0.5

    def kernel(li_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
               sems, nxt_ref):
        # nxt_ref[0]: the buffer the next chunk to compute lands in;
        # nxt_ref[1]: whether the row before has started this row's first
        # chunk.
        row = pl.program_id(0)
        layer, length = li_ref[0], len_ref[row]
        chunks = pl.cdiv(length, tc)

        def first(c):
            # A last chunk that would pass the row's end starts earlier,
            # and attends only what the chunk before it did not.
            return jnp.minimum(c * tc, t - tc)

        def copies(r, c, buf):
            return [pltpu.make_async_copy(
                hbm.at[layer, r, pl.ds(first(c), tc)], vmem.at[buf],
                sems.at[s, buf])
                for s, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))]

        def start(r, c, buf):
            for copy in copies(r, c, buf):
                copy.start()

        @pl.when(row == 0)
        def _first_row():
            nxt_ref[0] = 0
            nxt_ref[1] = 0

        @pl.when((chunks > 0) & (nxt_ref[1] == 0))
        def _own_first_chunk():
            start(row, 0, nxt_ref[0])

        nxt_ref[1] = 0
        qh = q_ref[...]

        def body(c, carry):
            m, l, acc = carry
            buf = nxt_ref[0]
            other = 1 - buf

            @pl.when(c + 1 < chunks)
            def _next_chunk():
                start(row, c + 1, other)

            after = jnp.minimum(row + 1, b - 1)

            @pl.when((c + 1 == chunks) & (row + 1 < b)
                     & (len_ref[after] > 0))
            def _next_rows_first_chunk():
                start(after, 0, other)
                nxt_ref[1] = 1

            nxt_ref[0] = other
            k_copy, v_copy = copies(row, c, buf)
            k_copy.wait()
            k = k_buf[buf].reshape(tc * hkv, hd)
            s = lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            head = lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            pos = first(c) + col // hkv
            seen = (col % hkv == head) & (pos >= c * tc) & (pos < length)
            s = jnp.where(seen, s * sm_scale, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            e = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            fade = jnp.exp(m - m_new)
            v_copy.wait()
            v = v_buf[buf].reshape(tc * hkv, hd)
            acc = acc * fade + jnp.dot(e.astype(v.dtype), v,
                                       preferred_element_type=jnp.float32)
            return m_new, l * fade + e.sum(axis=-1, keepdims=True), acc

        _, l, acc = lax.fori_loop(
            0, chunks, body,
            (jnp.full((hq, 1), _NEG_INF, jnp.float32),
             jnp.zeros((hq, 1), jnp.float32),
             jnp.zeros((hq, hd), jnp.float32)))
        o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    q_spec = pl.BlockSpec((None, hq, hd), lambda row, *_: (row, 0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, tc, hkv, hd), k_store.dtype),
                pltpu.VMEM((2, tc, hkv, hd), v_store.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dense_decode_attention",
    )(jnp.reshape(li, (1,)).astype(jnp.int32),
      jnp.clip(lengths, 0, t).astype(jnp.int32), q, k_store, v_store)


def dense_decode_attention(q, k_store, v_store, li, lengths, *,
                           n_kv_heads: int,
                           implementation: str | None = None,
                           interpret: bool = False):
    """Single-token attention over cache layer ``li`` of the dense K/V
    store, each row over the positions it holds.

    q: [B, Hq, hd] (one decode token per row, already rotary-embedded);
    k_store/v_store: the WHOLE stores [cache layers, B, T, Hkv, hd]; li:
    the cache layer, traced or not; lengths: [B] — row ``b`` attends
    positions ``< lengths[b]``, and a row of length 0 (a free slot, a row
    that emits nothing this step) reads nothing and yields zeros. Returns
    [B, Hq, hd] in q's dtype.

    ``implementation``: None (:func:`dense_decode_implementation`),
    "pallas" or "xla". An explicit "pallas" that cannot be compiled for
    this backend or shape raises (``interpret=True`` runs it in the
    interpreter anywhere). The kernel reads ``lengths[b]`` positions a row
    in place; the XLA path slices the layer out whole and masks."""
    b, hq, hd = q.shape
    if hq % n_kv_heads:
        raise ValueError(
            f"query heads {hq} not a multiple of kv heads {n_kv_heads}")
    if implementation is None:
        implementation = dense_decode_implementation(hd, k_store.dtype)
    elif implementation == "pallas" and not interpret:
        if why := _dense_kernel_unsupported(hd, k_store.dtype):
            raise ValueError(
                f"dense_decode_attention(implementation='pallas'): {why}")
    if implementation == "pallas":
        return _dense_decode_pallas(q, k_store, v_store, li, lengths,
                                    interpret=interpret)
    if implementation == "xla":
        return _dense_decode_xla(
            q.reshape(b, n_kv_heads, hq // n_kv_heads, hd), k_store, v_store,
            li, lengths).reshape(b, hq, hd)
    raise ValueError(f"unknown implementation {implementation!r}")


def _paged_span_xla(qg, k_pool, v_pool, table, pos, sm_scale):
    """Blockwise online-softmax walk for an S-wide query span. qg:
    [B, S, Hkv, G, hd]; pools: [N, Bs, Hkv, hd] (or quantized dicts);
    table: [B, MB]; pos: [B] — row ``b``'s span token ``s`` attends
    virtual positions ``<= pos[b] + s`` (its own just-written K/V
    included). Returns [B, S, Hkv, G, hd] f32; the dense
    ``[B, MB*Bs]`` view is never built."""
    n, bs = _kv_payload(k_pool).shape[0], _kv_payload(k_pool).shape[1]
    b, s_w, hkv, g, hd = qg.shape
    mb = table.shape[1]
    q32 = qg.astype(jnp.float32)
    # Per-(row, span-token) attention limit.
    limit = pos[:, None] + jnp.arange(s_w)[None, :]  # [B, S]

    def step(carry, j):
        m, l, acc = carry
        blk = jnp.clip(table[:, j], 0, n - 1)  # sentinels clamp; masked
        k_b = _read_block(k_pool, blk)
        v_b = _read_block(v_pool, blk)
        s = jnp.einsum("bskgd,bzkd->bkgsz", q32, k_b,
                       preferred_element_type=jnp.float32) * sm_scale
        span = j * bs + jnp.arange(bs)[None, None, :]
        mask = span <= limit[:, :, None]  # [B, S, Bs]
        s = jnp.where(mask[:, None, None, :, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bkgsz,bzkd->bkgsd", p, v_b)
        return (m_new, l, acc), None

    init = (
        jnp.full((b, hkv, g, s_w, 1), _NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, g, s_w, 1), jnp.float32),
        jnp.zeros((b, hkv, g, s_w, hd), jnp.float32),
    )
    (m, l, acc), _ = lax.scan(step, init, jnp.arange(mb))
    ok = m > _NEG_INF / 2  # rows parked past the table see no key
    out = jnp.where(ok, acc / jnp.where(l == 0.0, 1.0, l), 0.0)
    return out.transpose(0, 3, 1, 2, 4)  # [B, S, Hkv, G, hd]


def paged_span_attention(q, k_pool, v_pool, table, pos, *,
                         n_kv_heads: int, scale: float | None = None,
                         mesh=None, axis: str = "tensor"):
    """Fused S-wide attention over a paged KV pool — the span sibling of
    :func:`paged_decode_attention` (verify scoring reads [slots, K]
    spans, suffix prefill reads one [1, S] span; both previously paid
    the dense gather every layer).

    q: [B, S, Hq, hd] (already rotary-embedded, K/V for the span already
    scattered into the pool); pools/table as in
    :func:`paged_decode_attention`; pos: [B] — span token ``s`` of row
    ``b`` attends virtual positions ``<= pos[b] + s``. Returns
    [B, S, Hq, hd] f32. XLA block walk on every backend (the S-wide
    kernel shares the decode kernel's contract and can ride the same
    scalar-prefetch scheme later; the walk already removes the dense
    materialization, which is the bandwidth bill).

    ``mesh``/``axis``: the tensor-parallel twin, identical contract to
    :func:`paged_decode_attention`'s — each shard walks the same table
    over its local KV heads, no collective until the output
    projection."""
    b, s_w, hq, hd = q.shape
    if hq % n_kv_heads:
        raise ValueError(
            f"query heads {hq} not a multiple of kv heads {n_kv_heads}")
    group = hq // n_kv_heads
    sm_scale = (hd ** -0.5) if scale is None else scale
    qg = q.reshape(b, s_w, n_kv_heads, group, hd)
    if _shard_heads(mesh, axis, n_kv_heads) > 1:
        from jax.sharding import PartitionSpec as P

        from kubeflow_tpu.parallel.collectives import shard_map

        def _local(qg_l, k_l, v_l, tbl, pos_l):
            return _paged_span_xla(qg_l, k_l, v_l, tbl, pos_l, sm_scale)

        out = shard_map(
            _local, mesh=mesh,
            in_specs=(P(None, None, axis, None, None),
                      _pool_head_specs(k_pool, axis),
                      _pool_head_specs(v_pool, axis), P(), P()),
            out_specs=P(None, None, axis, None, None),
            axis_names=frozenset({axis}),
        )(qg, k_pool, v_pool, table, pos)
    else:
        out = _paged_span_xla(qg, k_pool, v_pool, table, pos, sm_scale)
    return out.reshape(b, s_w, hq, hd)


def ring_span_attention(q, k, v, pos, *, n_kv_heads: int,
                        scale: float | None = None,
                        mesh=None, axis: str = "sequence"):
    """Context-parallel exact span attention — the chunked-prefill ring.

    q: [B, S, Hq, hd] (one prefill chunk, already rotary-embedded; its
    K/V already scattered into the pool); k, v: [B, T, Hkv, hd] — the
    gathered, dequantized virtual rows (T = block_table width × block
    size, junk beyond the written span is exact zeros); pos: [B] — span
    token ``s`` of row ``b`` attends virtual positions ``<= pos[b] + s``
    (its own just-written K/V included). Returns [B, S, Hq, hd] f32.

    ``mesh`` with a ``sequence`` axis sized > 1 selects the ring twin:
    the query chunk is sharded S/cp per device and the K/V view T/cp per
    device; each device folds all cp K/V blocks with
    ring_attention's collective-permute online-softmax core
    (parallel/ring_attention.py:_block_attn), so per-device attention
    memory is O(S/cp × T/cp) and one replica's max prompt scales with
    cp. The span mask is computed from GLOBAL positions
    (ring_attention.py:span_bias), so the result is the same math as the
    dense read — f32-equivalent, not bitwise (online-softmax
    accumulation order differs), the same caveat as the fused block-walk
    kernels. GQA broadcasts K/V to query-head width before the ring
    (chunk views are bounded, so the width cost is the q block's)."""
    from kubeflow_tpu.parallel.ring_attention import (
        _block_attn,
        span_bias,
    )

    b, s_w, hq, hd = q.shape
    t_w = k.shape[1]
    if hq % n_kv_heads:
        raise ValueError(
            f"query heads {hq} not a multiple of kv heads {n_kv_heads}")
    group = hq // n_kv_heads
    sm_scale = (hd ** -0.5) if scale is None else scale
    # [B, T, H, hd] -> f32 [B, Hq, T, hd] with K/V at query-head width.
    qh = q.astype(jnp.float32).transpose(0, 2, 1, 3)
    kh = jnp.repeat(k.astype(jnp.float32), group, axis=2).transpose(0, 2, 1, 3)
    vh = jnp.repeat(v.astype(jnp.float32), group, axis=2).transpose(0, 2, 1, 3)

    def _fold_all(qh_l, kh_l, vh_l, pos_l, q_start, k_start):
        m0 = jnp.full((b, hq, qh_l.shape[2], 1), _NEG_INF, jnp.float32)
        num0 = jnp.zeros(qh_l.shape, jnp.float32)
        den0 = jnp.zeros((b, hq, qh_l.shape[2], 1), jnp.float32)
        bias = span_bias(pos_l, q_start, k_start,
                         qh_l.shape[2], kh_l.shape[2])[:, None]
        return _block_attn(qh_l, kh_l, vh_l, bias, m0, num0, den0, sm_scale)

    shards = int(mesh.shape.get(axis, 1)) if mesh is not None else 1
    if shards <= 1:
        m, num, den = _fold_all(qh, kh, vh, pos, 0, 0)
        return (num / den).transpose(0, 2, 1, 3)

    if s_w % shards or t_w % shards:
        raise ValueError(
            f"chunk width {s_w} and virtual width {t_w} must divide the "
            f"{shards}-way {axis!r} axis")
    from jax.sharding import PartitionSpec as P

    from kubeflow_tpu.parallel.collectives import (
        axis_size,
        shard_map,
    )

    def _ring(qh_l, kh_l, vh_l, pos_l):
        n = axis_size(axis)
        idx = lax.axis_index(axis)
        s_loc, t_loc = qh_l.shape[2], kh_l.shape[2]

        def step(carry, i):
            k_blk, v_blk, m, num, den = carry
            # Block i arrived from device (idx + i) mod n — its global
            # key offset; the query offset is this device's fixed chunk
            # slice. Global coordinates keep the mask exact across the
            # ring, fully-masked far blocks flush to exact zero when a
            # real block folds (the finite -1e30 trick).
            src = (idx + i) % n
            bias = span_bias(pos_l, idx * s_loc, src * t_loc,
                             s_loc, t_loc)[:, None]
            m, num, den = _block_attn(qh_l, k_blk, v_blk, bias,
                                      m, num, den, sm_scale)
            perm = [(j, (j - 1) % n) for j in range(n)]
            k_nxt = lax.ppermute(k_blk, axis_name=axis, perm=perm)
            v_nxt = lax.ppermute(v_blk, axis_name=axis, perm=perm)
            return (k_nxt, v_nxt, m, num, den), None

        m0 = jnp.full((b, hq, s_loc, 1), _NEG_INF, jnp.float32)
        num0 = jnp.zeros(qh_l.shape, jnp.float32)
        den0 = jnp.zeros((b, hq, s_loc, 1), jnp.float32)
        (_k, _v, m, num, den), _ = lax.scan(
            step, (kh_l, vh_l, m0, num0, den0), jnp.arange(n))
        return num / den

    out = shard_map(
        _ring, mesh=mesh,
        in_specs=(P(None, None, axis, None), P(None, None, axis, None),
                  P(None, None, axis, None), P()),
        out_specs=P(None, None, axis, None),
        axis_names=frozenset({axis}),
    )(qh, kh, vh, pos)
    return out.transpose(0, 2, 1, 3)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    scale: float | None = None,
    kv_mask=None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int | None = None,
    implementation: str | None = None,
    mesh=None,
):
    """Multi-head / grouped-query flash attention.

    q: [B, T, H_q, D]; k, v: [B, S, H_kv, D] with H_q a multiple of H_kv.
    ``kv_mask``: optional [B, S], truthy = attend (padding mask for BERT /
    batched serving). Returns [B, T, H_q, D]. ``implementation``:

    - None — auto: the splash kernel on TPU for supported shapes at
      T ≥ 512 (where its causal block skipping and GQA-native layout win;
      measured +5 to +18 MFU pts on flagship-deep), blockwise XLA
      otherwise.
    - "splash" — GQA-native tiled TPU kernel (fused bwd, block-sparse
      causal masking, residuals checkpoint-nameable as "attn_res").
    - "pallas" — tiled TPU flash kernel (fused bwd + causal block
      skipping; K/V broadcast to H_q width).
    - "xla" — blockwise online-softmax scan (any backend, any shape).
    - "plain" — materialized scores.

    Auto falls to the XLA path off-TPU or for masked/unaligned shapes,
    so one model definition runs everywhere; an EXPLICIT "splash" or
    "pallas" that cannot be honoured raises instead of quietly running
    something else. ``mesh``: the mesh the caller's jit is partitioned
    over; with more than one device the kernel runs under ``shard_map``
    (batch over data*fsdp, heads over tensor).
    """
    b, t, hq, d = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    scale = (d**-0.5) if scale is None else scale

    if implementation in (None, "splash", "pallas"):
        why_not = _kernel_unsupported(q, k, kv_mask, mesh)
        if implementation is None and t >= 512 and not why_not:
            implementation = "splash"
        elif implementation is not None and why_not:
            raise ValueError(
                f"flash_attention(implementation={implementation!r}): "
                f"{why_not}")
    if implementation in ("splash", "pallas"):
        # block_k=None → per-path measured-best default: 1024-wide tiles
        # here (2048 is slower at seq≥2048 and a VMEM risk), 2048 on the
        # XLA path below. An explicit block_k is honored as given.
        kernel = functools.partial(
            _pallas_flash if implementation == "pallas" else _splash_flash,
            causal=causal, scale=scale,
            block=DEFAULT_KERNEL_BLOCK_K if block_k is None else block_k)
        if mesh is not None and mesh.size > 1:
            kernel = _shard_kernel(kernel, mesh)
        return kernel(q, k, v)
    if block_k is None:
        block_k = DEFAULT_BLOCK_K

    if kv_mask is None:
        kvm = jnp.ones((b, s_len), jnp.float32)
    else:
        kvm = kv_mask.astype(jnp.float32)
    kvm = jnp.repeat(kvm[:, None], hkv, axis=1).reshape(b * hkv, s_len, 1)

    # [B, T, Hq, D] -> [B*Hkv, G, T, D]; K/V -> [B*Hkv, S, D].
    qf = (
        q.transpose(0, 2, 1, 3)
        .reshape(b, hkv, group, t, d)
        .reshape(b * hkv, group, t, d)
    )
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s_len, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s_len, d)

    if implementation == "plain":
        # Materialized scores; plain autodiff (no flash recompute) — the
        # short-sequence fast path where O(T·S) memory is cheap.
        out = _plain_attention(qf, kf, vf, kvm, causal=causal, scale=scale)
    else:
        out = _flash(qf, kf, vf, kvm, causal, scale, block_q, block_k)
    return (
        out.reshape(b, hkv, group, t, d)
        .reshape(b, hq, t, d)
        .transpose(0, 2, 1, 3)
    )
