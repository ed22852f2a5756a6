"""Autoregressive decoding with a KV cache for the transformer family.

TPU-first incremental decoding: one prefill pass fills the cache for the
whole (right-padded) prompt batch, then ``lax.scan`` decodes in lockstep —
every step is a fixed-shape single-token forward against the cache, so the
whole generate call is ONE compiled executable (no per-token dispatch, no
shape churn). Ragged prompts are handled with a per-row validity mask and
per-row RoPE positions: row ``b``'s token at decode step ``t`` carries true
position ``length[b] + t`` even though it lives at cache slot ``T0 + t``.

The reference serves generation through TF-Serving's black-box ModelServer;
this is the equivalent capability for the platform's own engine
(kubeflow/tf-serving/tf-serving-template.libsonnet:29-49 surface).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from kubeflow_tpu.observability.tracing import (
    SCOPE_ATTN,
    SCOPE_DECODE,
    SCOPE_EMBED,
    SCOPE_HEAD,
    SCOPE_LATENT_ATTN,
    SCOPE_MLP,
    SCOPE_PREFILL,
    SCOPE_SAMPLE,
    SCOPE_SPARSE_ATTN,
    SCOPE_SPARSE_SELECT,
    scope,
)
from kubeflow_tpu.ops import rms_norm
from kubeflow_tpu.ops.attention import (
    dense_decode_attention,
    dense_decode_implementation,
    paged_decode_attention,
    paged_span_attention,
    ring_span_attention,
)
from kubeflow_tpu.ops.rotary import rotary_frequencies
from kubeflow_tpu.ops.sparse_attention import (
    compress_keys,
    compress_last,
    select_blocks,
    sparse_decode_attention,
)
from kubeflow_tpu.models.transformer import (
    MIXER_LIGHTNING,
    MIXER_SPARSE,
    TransformerConfig,
    cast_param,
    close_pass,
    final_hidden,
    head_kernel,
    latent_ffn,
    lightning_span,
    lightning_token,
    mixer_out,
    mixer_qkv,
    mla_expanded,
    mla_inputs,
    moe_ffn,
    post_norm,
    project,
    rotate_half,
    sparse_span,
)

_NEG_INF = -1e30


def init_cache(cfg: TransformerConfig, batch: int, total_len: int):
    """K/V cache ``[cache layers, batch, total_len, Hkv, hd]``, stacked on
    a leading dim like the params: one entry per layer, and of a looped
    stack one per (pass, layer) at ``pass * n_layers + layer``
    (``cfg.cache_layers``). The decode state's cache is carried whole
    through the layer loop and written in place at ``[layer, ...]``
    (:func:`_layer_loop` says why it is not scanned layer by layer)."""
    if cfg.mixer_types:
        raise ValueError(
            "mixer_types: the dense KV cache (and the lockstep generate "
            "built on it) holds K and V for every layer and nothing else; "
            "a model with recurrent state serves on kv_layout='paged'")
    if cfg.kv_lora_rank:
        # One row a token and layer, the latent and the rotary key that
        # every head reads (``v`` holds nothing).
        return {"k": jnp.zeros((cfg.cache_layers, batch, total_len,
                                cfg.latent_width), cfg.dtype), "v": None}
    shape = (cfg.cache_layers, batch, total_len, cfg.n_kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _scratch_len(cfg: TransformerConfig, t0: int, total: int,
                 block: int = 1) -> int:
    """Positions a row of an admission's scratch cache holds: the whole
    row, which also clears what the slot's last user left. A looped
    stack's scratch is ``n_passes`` times the layers (2 GB a row of 1,280
    at Ouro-2.6B's sizes, beside 13.4 GB held), so it holds the prompt's
    positions alone, to a whole block: what lies past them in the slot is
    masked until a decode step has written it."""
    if cfg.n_passes == 1:
        return total
    return min(total, -(-t0 // block) * block)


def _gqa_attention(q, k_cache, v_cache, mask, cfg):
    """Grouped-query attention over a KV cache, GQA-native: the query-
    head group rides its own einsum axis, so K/V are read at kv-head
    width — never repeated to H_q width (a 2-8x cut in decode cache
    traffic, the decode-step bandwidth bill). q: [B, S, H, hd]; cache:
    [B, T, Hkv, hd]; mask broadcastable to [B, Hkv, G, S, T]. Returns
    [B, S, H*hd]."""
    b, s, _h, hd = q.shape
    group = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, s, cfg.n_kv_heads, group, hd)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32),
        k_cache.astype(jnp.float32)
    ) * (hd ** -0.5)
    scores = jnp.where(mask, scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", p, v_cache).reshape(
        b, s, cfg.n_heads * hd)


@scope(SCOPE_ATTN)
def _cached_attention(x, layer, cfg, rope_bt, k_cache, v_cache, pos, valid,
                      li=None):
    """x: [B, S, D] at cache slots pos..pos+S; attends over the full cache
    masked by ``valid`` [B, total]. Returns (out, k_cache, v_cache). With
    ``li`` (traced) the two are the WHOLE cache, every cache layer,
    written and read in place at ``[li]`` as :func:`_ragged_attention`
    does."""
    s = x.shape[1]
    q, k, v = _qkv_rope(x, layer, cfg, rope_bt)
    if li is None:
        k_cache = lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
        v_cache = lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
        k_rows, v_rows = k_cache, v_cache
    else:
        k_cache = lax.dynamic_update_slice(k_cache, k[None],
                                           (li, 0, pos, 0, 0))
        v_cache = lax.dynamic_update_slice(v_cache, v[None],
                                           (li, 0, pos, 0, 0))
        k_rows, v_rows = _layer_of(k_cache, li), _layer_of(v_cache, li)

    total = k_rows.shape[1]
    # Causality within the new block: query at slot pos+i sees key slot j
    # iff j <= pos+i; prompt padding and unwritten slots are masked by
    # ``valid`` (which already includes slots pos..pos+S for this block).
    j_idx = jnp.arange(total)[None, None, :]
    i_idx = pos + jnp.arange(s)[None, :, None]
    mask = (j_idx <= i_idx) & valid[:, None, :]
    out = _gqa_attention(q, k_rows, v_rows, mask[:, None, None], cfg)
    return out @ cast_param(layer["wo"], cfg.dtype), k_cache, v_cache


@scope(SCOPE_ATTN)
def _latent_cached_attention(x, attn, cfg, rope_bt, store, pos, valid, li):
    """The expanded latent attention of cache layer ``li`` for x [B, S, D]
    at slots pos..pos+S: the block's rows written into the WHOLE latent
    store [L, B, T, W] in place, every head's K/V expanded from the rows
    the block can see (those before ``pos + S`` where ``pos`` is a Python
    int, as at every admission; the whole row where it is traced), masked
    by ``valid`` [B, T] and causality. Returns (out, store, None)."""
    s = x.shape[1]
    q_nope, q_rope, row = mla_inputs(x, attn, cfg, rope_bt)
    store = lax.dynamic_update_slice(store, row[None].astype(store.dtype),
                                     (li, 0, pos, 0))
    rows = _layer_of(store, li)
    if isinstance(pos, int):
        rows, valid = rows[:, :pos + s], valid[:, :pos + s]
    j_idx = jnp.arange(rows.shape[1])[None, None, :]
    i_idx = pos + jnp.arange(s)[None, :, None]
    mask = (j_idx <= i_idx) & valid[:, None, :]
    return mla_expanded(q_nope, q_rope, rows, mask, attn, cfg), store, None


@scope(SCOPE_ATTN)
def _latent_attention(x, attn, cfg, rope_bt, store, li, pos_b, token_valid,
                      write_index):
    """Single-token latent attention of cache layer ``li`` where row ``b``
    writes slot ``pos_b[b]``, in the absorbed form: the row's latent and
    rotary key written into the whole store [L, B, T, W] in place, each
    head's query taken into latent space (q_nope through its ``w_uk``) and
    beside the rotary query, ONE read of the store (every head a product
    over the same rows, ops/attention.py:dense_decode_attention, bounded
    by what each emitting row holds), the latent context out through each
    head's ``w_uv``. The same function as :func:`_latent_cached_attention`
    with the up-projections moved across the products. Returns
    (out, store, None)."""
    b = x.shape[0]
    h, rank = cfg.n_heads, cfg.kv_lora_rank
    dtype = cfg.dtype
    q_nope, q_rope, row = mla_inputs(x, attn, cfg, rope_bt)
    store = _dense_write(store, li, write_index, row)
    with scope(SCOPE_LATENT_ATTN):
        q_lat = jnp.einsum("bhn,hnr->bhr", q_nope[:, 0],
                           cast_param(attn["w_uk"], dtype))
        q = jnp.concatenate(
            [q_lat.astype(dtype), q_rope[:, 0],
             jnp.zeros((b, h, store.shape[-1] - rank - cfg.qk_rope_head_dim),
                       dtype)], axis=-1)
        o_lat = dense_decode_attention(
            q, store, None, li, jnp.where(token_valid, pos_b + 1, 0),
            n_kv_heads=1, v_dim=rank,
            sm_scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)
        o = jnp.einsum("bhr,hrv->bhv", o_lat, cast_param(attn["w_uv"], dtype))
    o = o.reshape(b, 1, h * cfg.v_head_dim).astype(dtype)
    return o @ cast_param(attn["wo"], dtype), store, None


def _qkv_rope(x, layer, cfg, rope_bt):
    """x [B, S, D] → rotary-embedded q [B, S, H, hd], k and v
    [B, S, Hkv, hd]; ``rope_bt`` is (cos, sin) [B, S, hd//2] gathered per
    row by the caller, which also holds the ``attn`` scope."""
    b, s, _d = x.shape
    hd = cfg.head_dim
    cos, sin = rope_bt
    q = project(x, layer, "q", cfg.dtype).reshape(b, s, cfg.n_heads, hd)
    k = project(x, layer, "k", cfg.dtype).reshape(b, s, cfg.n_kv_heads, hd)
    v = project(x, layer, "v", cfg.dtype).reshape(b, s, cfg.n_kv_heads, hd)
    return rotate_half(q, cos, sin), rotate_half(k, cos, sin), v


def _embed(params, tokens, cfg):
    with scope(SCOPE_EMBED):
        return cast_param(params["embed"]["kernel"], cfg.dtype)[tokens]


def _ffn(h, mlp, cfg, token_valid):
    """A layer's feed-forward half on normed ``h``: MoE (pad tokens claim
    no expert capacity) or the dense SwiGLU."""
    if cfg.n_experts:
        return moe_ffn(h, mlp, cfg, token_valid=token_valid)[0]
    with scope(SCOPE_MLP):
        gate = h @ cast_param(mlp["gate"], cfg.dtype)
        up = h @ cast_param(mlp["up"], cfg.dtype)
        return (jax.nn.silu(gate) * up) @ cast_param(mlp["down"], cfg.dtype)


def _head(params, x, cfg):
    """Final-norm hidden ``x`` → float32 logits."""
    with scope(SCOPE_HEAD):
        return (x @ head_kernel(params, cfg)).astype(jnp.float32)


def forward_cached(params, tokens, cfg: TransformerConfig, cache, pos,
                   positions, valid, token_valid=None):
    """tokens [B, S] at cache slots pos..pos+S with true sequence positions
    ``positions`` [B, S] → (logits [B, S, V], new cache). ``token_valid``
    ([B, S]) marks real (non-pad) tokens in THIS block — MoE routing must
    not let ragged-prefill padding claim expert capacity."""
    cos_t, sin_t = rotary_frequencies(cfg.rope_dim, cache["k"].shape[2],
                                      theta=cfg.rope_theta)
    rope_bt = (cos_t[positions], sin_t[positions])
    x = _embed(params, tokens, cfg)
    if cfg.kv_lora_rank:
        # The expanded form (every head's K/V from the latent) over the
        # rows the block can see, the store riding the layer loop.
        def attend(h, attn, store, _none, li):
            return _latent_cached_attention(h, attn, cfg, rope_bt, store,
                                            pos, valid, li)

        logits, store, _, _ = _layer_loop(params, cfg, x, cache["k"], None,
                                          attend, token_valid)
        return logits, {"k": store, "v": None}
    if cfg.n_passes > 1:
        # A looped stack cannot scan a cache of n_passes * n_layers
        # entries beside n_layers layers of parameters: the cache rides
        # THE layer loop as its carry, as a decode step's does.
        def attend(h, attn, k_all, v_all, li):
            return _cached_attention(h, attn, cfg, rope_bt, k_all, v_all,
                                     pos, valid, li)

        logits, k_new, v_new, _ = _layer_loop(params, cfg, x, cache["k"],
                                              cache["v"], attend, token_valid)
        return logits, {"k": k_new, "v": v_new}

    def layer_fn(x, layer_and_cache):
        layer, k_cache, v_cache = layer_and_cache
        h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
        attn, k_cache, v_cache = _cached_attention(
            h, layer["attn"], cfg, rope_bt, k_cache, v_cache, pos, valid
        )
        x = x + post_norm(attn, layer, "ln_attn_post", cfg)
        h = rms_norm(x, layer["ln_mlp"], eps=cfg.norm_eps)
        x = x + post_norm(_ffn(h, layer["mlp"], cfg, token_valid), layer,
                          "ln_mlp_post", cfg)
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"])
    )
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    return _head(params, x, cfg), {"k": k_new, "v": v_new}


def _top_k_mask(logits, top_k: int):
    """Mask everything below the k-th logit to -inf (no-op for top_k=0)."""
    if top_k and top_k < logits.shape[-1]:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    return logits


@scope(SCOPE_SAMPLE)
def sample_token(logits, key, temperature, top_k: int = 0):
    """logits [B, V], temperature [B] (<=0 → greedy), static top_k."""
    greedy = jnp.argmax(logits, axis=-1)
    logits = _top_k_mask(logits, top_k)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(key, logits / temp, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens",
                                             "top_k"))
def generate(params, prompt_tokens, prompt_lengths, cfg: TransformerConfig,
             *, max_new_tokens: int, key, temperature, top_k: int = 0,
             row_valid=None):
    """prompt_tokens [B, T0] right-padded, prompt_lengths [B] →
    (generated [B, max_new_tokens], prefill_logits [B, V]).

    ``temperature`` [B]: <=0 rows decode greedily. ``row_valid`` [B] marks
    real instances in a server-padded batch — pad rows must not claim MoE
    expert capacity during decode and evict real tokens' expert choices.
    One compiled call: prefill + a scanned decode loop over the KV cache.
    """
    b, t0 = prompt_tokens.shape
    total = t0 + max_new_tokens
    cache = init_cache(cfg, b, total)
    # A zero-length row would wrap the last-logit gather to index -1 (the
    # last prefill slot) and seed generation from garbage; clamp to 1 so
    # the behavior is defined even if callers skip engine validation.
    prompt_lengths = jnp.maximum(prompt_lengths, 1)
    if row_valid is None:
        row_valid = jnp.ones((b,), bool)

    slot = jnp.arange(total)[None, :]
    valid = slot < prompt_lengths[:, None]  # prompt slots only
    positions = jnp.broadcast_to(jnp.arange(t0)[None], (b, t0))
    logits, cache = forward_cached(
        params, prompt_tokens, cfg, cache, 0, positions, valid,
        token_valid=(jnp.arange(t0)[None] < prompt_lengths[:, None])
        & row_valid[:, None],
    )
    last = jnp.take_along_axis(
        logits, (prompt_lengths - 1)[:, None, None], axis=1
    )[:, 0]

    def step(carry, i):
        cache, valid, tok, logits_prev, key = carry
        key, sub = jax.random.split(key)
        tok = sample_token(logits_prev, sub, temperature, top_k)
        slot_i = t0 + i
        valid = valid.at[:, slot_i].set(True)
        pos_i = (prompt_lengths + i)[:, None]  # true position per row
        logits, cache = forward_cached(
            params, tok[:, None], cfg, cache, slot_i, pos_i, valid,
            token_valid=row_valid[:, None],
        )
        return (cache, valid, tok, logits[:, 0], key), tok

    (_, _, _, _, _), toks = lax.scan(
        step, (cache, valid, jnp.zeros((b,), jnp.int32), last, key),
        jnp.arange(max_new_tokens),
    )
    return toks.T, last  # [B, max_new], [B, V]


# ---------------------------------------------------------------------------
# Continuous-batching primitives (serving/continuous.py drives these)
# ---------------------------------------------------------------------------
#
# The lockstep ``generate`` above compiles prefill+decode into one call — the
# right shape for offline batches, the wrong one for a server: every request
# waits for the slowest peer. The continuous path splits the work into
# fixed-shape executables so the scheduler can retire/admit rows between
# steps: ``admit_rows_and_step`` (prefill a round's admissions, scatter them
# into the persistent state, and take one decode step — one dispatch) and
# ``decode_step``/``decode_chunk`` (one token / K fused tokens for ALL
# slots). ``prefill`` + ``insert_row`` remain as the unfused admission
# pieces (callers that need the row cache itself). Unlike ``generate``'s
# shared scalar ``pos``, rows here sit at *different* sequence positions,
# so the cache write and attention mask are per-row.
#
# The persistent K/V storage (dense cache or paged pool) is CARRIED WHOLE
# through the layer loop (``_layer_loop``): each layer scatters its token
# at ``[layer, ...]`` of the whole array and attends over ``store[layer]``
# read where it lies, so a donated state is updated in place. It is
# deliberately not the scan's ``xs``/``ys``: a scan cannot alias an input
# it slices with the output it stacks, so XLA keeps a second whole store,
# slices each layer out of the first, writes the slice into the second
# and copies one over the other every step (0.6 GB of temporaries and
# 2.5 ms of an 8.1 ms step at 6 layers x 32 slots x 768 on a v5e, dense
# and paged alike; tests/test_tpu_compile.py holds the compile to it).


def _kv_arr(pool):
    """Payload array of a KV block pool — the int8 codes when the pool
    is quantized (``{"q", "scale"}``), the pool itself otherwise. Shape
    queries (block size, layer count) go through this so every caller
    is layout- AND precision-agnostic."""
    return pool["q"] if isinstance(pool, dict) else pool


def _quantize_kv(vals):
    """Abs-max int8 quantization of K/V values ``[..., H, hd]`` with one
    f32 scale per (position, head): ``{"q": int8, "scale": [..., H]}``.
    All-zero vectors (freshly admitted padding) map to scale 0 → exact
    zeros on dequant."""
    v32 = vals.astype(jnp.float32)
    scale = jnp.max(jnp.abs(v32), axis=-1) / 127.0
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(v32 / safe[..., None]), -127, 127)
    return {"q": q.astype(jnp.int8), "scale": scale}


def _pool_gather(pool, layer, table):
    """Read layer ``layer`` of the whole block pool ``[L, N, Bs, H, hd]``
    through block table ``[B, MB]`` into virtual rows ``[B, MB*Bs, H, hd]``
    — virtual position ``p`` of row ``b`` lives at block
    ``table[b, p // Bs]``, offset ``p % Bs``. The layer rides the gather's
    index (``layer`` may be traced), so no layer is sliced out first.
    Sentinel entries (``>= N``, the unallocated marker) clamp to the last
    block; the junk they surface sits in positions the validity mask
    already excludes, so it contributes exact zeros. Quantized pools
    dequantize after the gather (this materialized path is the reference;
    the fused kernel dequantizes in-register)."""
    b = table.shape[0]
    if isinstance(pool, dict):
        h, hd = pool["q"].shape[3:]
        q = pool["q"][layer, table].reshape(b, -1, h, hd).astype(jnp.float32)
        s = pool["scale"][layer, table].reshape(b, -1, h)
        return q * s[..., None]
    h, hd = pool.shape[3:]
    return pool[layer, table].reshape(b, -1, h, hd)


def _pool_write(pool, layer, table, cols, vals):
    """Scatter ``vals`` [B, S, H, hd] into layer ``layer`` of the whole
    pool ``[L, N, Bs, H, hd]`` at per-row virtual positions ``cols``
    [B, S] through the block table — one in-place scatter on the whole
    array. Out-of-range cols (rows parked at ``total``) and sentinel
    table entries resolve to a physical index past the pool, which
    scatter semantics drop — the paged twin of the dense path's
    parked-row no-op write. Quantized pools abs-max-quantize at scatter
    time: each written position's int8 codes and per-head scale land
    together, so a block's payload and its scales can never drift
    apart."""
    arr = _kv_arr(pool)
    n, bs = arr.shape[1], arr.shape[2]
    mb = table.shape[1]
    blk = jnp.take_along_axis(table, jnp.clip(cols // bs, 0, mb - 1), axis=1)
    blk = jnp.where((cols >= 0) & (cols < mb * bs), blk, n)
    if isinstance(pool, dict):
        qd = _quantize_kv(vals)
        return {"q": pool["q"].at[layer, blk, cols % bs].set(qd["q"]),
                "scale": pool["scale"].at[layer, blk, cols % bs].set(
                    qd["scale"])}
    return pool.at[layer, blk, cols % bs].set(vals)


def _layer_of(store, layer):
    """Layer ``layer`` (traced) of a whole K/V store, as a dynamic index
    XLA fuses into the consumer; a quantized pool yields both leaves."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        store)


_DENSE_WRITE = lax.ScatterDimensionNumbers(
    update_window_dims=(2, 3), inserted_window_dims=(0, 1, 2),
    scatter_dims_to_operand_dims=(0, 1, 2))
# The same into a latent store [L, B, T, W]: a position's row is one
# vector, not one a head.
_LATENT_WRITE = lax.ScatterDimensionNumbers(
    update_window_dims=(2,), inserted_window_dims=(0, 1, 2),
    scatter_dims_to_operand_dims=(0, 1, 2))


def _dense_write_index(cols):
    """The ``[B, S, 3]`` scatter index of a dense write at ``[0, row,
    cols[row]]``. :func:`_dense_write` adds the layer, so a caller builds
    the rest once, outside the layer loop, where an op runs once a step
    and not once a layer application (192 a step of a looped stack)."""
    b, s = cols.shape
    rows = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None], (b, s))
    return jnp.stack([jnp.zeros_like(rows), rows, cols.astype(jnp.int32)],
                     -1)


def _dense_write(store, layer, index, vals):
    """Write ``vals`` [B, S, Hkv, hd] into the dense cache at ``index``
    (:func:`_dense_write_index`) of layer ``layer``: one in-place scatter
    whose index needs no negative-index normalisation (none is negative);
    out-of-bounds positions are dropped. A latent store's ``vals`` are
    [B, S, W]."""
    return lax.scatter(
        store, index + jnp.asarray([1, 0, 0], jnp.int32) * layer,
        vals.astype(store.dtype),
        _DENSE_WRITE if store.ndim == 5 else _LATENT_WRITE,
        indices_are_sorted=True, unique_indices=True,
        mode=lax.GatherScatterMode.FILL_OR_DROP)


def _store_write(store, layer, table, cols, vals, index=None):
    """Write ``vals`` [B, S, Hkv, hd] at ``[layer, row, cols[row]]`` of
    the WHOLE K/V store, in place: the dense cache ``[L, B, T, Hkv, hd]``
    (``table`` None) or the paged pool through ``table``. Out-of-bounds
    cols (a retired row parked at ``total``, a span's spill past the
    cache tail) are dropped by scatter semantics — they write nowhere.
    ``index``: the dense write's :func:`_dense_write_index` of ``cols``,
    where a caller built it once outside its layer loop."""
    if table is None:
        if index is None:
            index = _dense_write_index(cols)
        return _dense_write(store, layer, index, vals)
    return _pool_write(store, layer, table, cols, vals)


def _store_rows(store, layer, table):
    """The rows ``[B, T, Hkv, hd]`` layer ``layer`` attends over, read
    from where they lie in the whole store: a dynamic index of the dense
    cache, a gather through ``table`` of the pool."""
    if table is None:
        return _layer_of(store, layer)
    return _pool_gather(store, layer, table)


@scope(SCOPE_ATTN)
def _ragged_attention(x, layer, cfg, rope_bt, k_store, v_store, li, pos_b,
                      valid, token_valid, table=None, fused=False,
                      mesh=None, write_index=None):
    """Single-token attention of layer ``li`` (traced: the layer loop's
    index) where row ``b`` writes cache slot ``pos_b[b]`` — the
    continuous-batching variant of :func:`_cached_attention` (rows at
    heterogeneous positions). x: [B, 1, D]; pos_b: [B]; valid: [B, total].

    ``k_store``/``v_store`` are the WHOLE K/V storage, all layers,
    written in place: the token's K/V is scattered at
    ``[li, row, pos_b[row]]`` and the attention reads ``store[li]`` where
    it lies (a dynamic index XLA fuses into its consumer) — no layer is
    sliced out and written back, which is what a cache scanned as
    ``xs``/``ys`` costs (:func:`_layer_loop`). A caller's layer loop
    hands the dense write its ``write_index`` (:func:`_dense_write_index`),
    built once outside the loop. Returns (out, k_store, v_store).

    Where the dense cache's kernel compiles (a TPU, ``head_dim`` a
    multiple of 128; ops/attention.py:dense_decode_attention) the read
    stops at what each row holds: positions ``<= pos_b`` of a row that
    emits (``token_valid``), nothing of one that does not, copied out of
    the whole store in place. Under a ``mesh`` the kernel could not be
    partitioned, and the XLA read stays.

    With ``table`` ([B, max_blocks]) the storage is the paged block pool
    ``[L, N, Bs, H, hd]``: the write scatters through the table and the
    attention reads the row gathered at block granularity — same math,
    same mask, so outputs are byte-identical to the dense layout. With
    ``fused`` the gather never happens: the block-table attention kernel
    (ops/attention.py:paged_decode_attention) walks the table with an
    online softmax, so the dense ``[B, total]`` view of the cache is
    never materialized (its numerics are f32-equivalent, not bitwise —
    the gather path stays the pinned-parity reference). The kernel takes
    one layer's pool (it relays it head-major for its tiles), so this arm
    alone still reads ``pool[li]`` out as an operand. ``mesh`` (a
    tensor-parallel serving mesh) routes the fused read through the
    kernel's shard_map twin: each shard walks the same table over its
    local KV heads."""
    b, s, _d = x.shape
    q, k, v = _qkv_rope(x, layer, cfg, rope_bt)
    k_store = _store_write(k_store, li, table, pos_b[:, None], k, write_index)
    v_store = _store_write(v_store, li, table, pos_b[:, None], v, write_index)
    if fused:
        # The decode step's validity mask is exactly "positions
        # <= pos_b" (the just-written token included), which is the
        # fused kernel's span contract.
        out = paged_decode_attention(
            q[:, 0], _layer_of(k_store, li), _layer_of(v_store, li), table,
            pos_b, n_kv_heads=cfg.n_kv_heads, mesh=mesh,
        ).reshape(b, s, cfg.n_heads * cfg.head_dim)
    elif (table is None and mesh is None and dense_decode_implementation(
            cfg.head_dim, k_store.dtype) == "pallas"):
        out = dense_decode_attention(
            q[:, 0], k_store, v_store, li,
            jnp.where(token_valid, pos_b + 1, 0), n_kv_heads=cfg.n_kv_heads,
        ).reshape(b, s, cfg.n_heads * cfg.head_dim)
    else:
        out = _gqa_attention(q, _store_rows(k_store, li, table),
                             _store_rows(v_store, li, table),
                             valid[:, None, None, None, :], cfg)
    # Quantized pools dequantize to f32; fold back to the compute dtype
    # (identity for fp pools) so the residual stream's dtype is stable.
    return (out.astype(cfg.dtype) @ cast_param(layer["wo"], cfg.dtype),
            k_store, v_store)


@functools.partial(jax.jit, static_argnames=("cfg", "total_len"))
@scope(SCOPE_PREFILL)
def prefill(params, prompt_tokens, prompt_lengths, cfg: TransformerConfig, *,
            total_len: int):
    """One request's prompt pass: tokens [B, T0] right-padded → (cache with
    ``total_len`` slots, last-position logits [B, V]). Slots beyond the true
    length hold pad junk; decode overwrites them before the mask admits them.
    """
    b, t0 = prompt_tokens.shape
    cache = init_cache(cfg, b, total_len)
    prompt_lengths = jnp.maximum(prompt_lengths, 1)
    valid = jnp.arange(total_len)[None, :] < prompt_lengths[:, None]
    positions = jnp.broadcast_to(jnp.arange(t0)[None], (b, t0))
    logits, cache = forward_cached(
        params, prompt_tokens, cfg, cache, 0, positions, valid,
        token_valid=positions < prompt_lengths[:, None],
    )
    last = jnp.take_along_axis(
        logits, (prompt_lengths - 1)[:, None, None], axis=1
    )[:, 0]
    return cache, last


def init_decode_state(cfg: TransformerConfig, slots: int, total_len: int,
                      seed: int = 0):
    """Persistent server decode state: ``slots`` in-flight rows over a shared
    fixed-shape KV cache. ``length`` is each row's next write slot (== tokens
    held so far); inactive rows are parked with ``active`` False. A model
    with the dropless expert layer also counts, on the device, the tokens
    each (expert layer, expert) took and the experts each expert layer
    touched, summed over the decode steps (``moe_routed`` [layers, E],
    ``moe_touched`` [layers])."""
    state = {
        "cache": init_cache(cfg, slots, total_len),
        "length": jnp.zeros((slots,), jnp.int32),
        "remaining": jnp.zeros((slots,), jnp.int32),
        "active": jnp.zeros((slots,), bool),
        "temperature": jnp.zeros((slots,), jnp.float32),
        "last_logits": jnp.zeros((slots, cfg.vocab_size), jnp.float32),
        "key": jax.random.PRNGKey(seed),
    }
    if cfg.kv_lora_rank:
        layers = cfg.n_layers - cfg.first_k_dense
        state["moe_routed"] = jnp.zeros((layers, cfg.n_experts), jnp.int32)
        state["moe_touched"] = jnp.zeros((layers,), jnp.int32)
    return state


@functools.partial(jax.jit, donate_argnames=("state",))
def insert_row(state, slot, row_cache, last_logits, length, remaining,
               temperature):
    """Copy a prefilled request (batch-1 ``prefill`` outputs) into row
    ``slot`` of the persistent state. ``slot`` is traced — one executable
    serves every slot index."""
    k = lax.dynamic_update_slice(
        state["cache"]["k"], row_cache["k"], (0, slot, 0, 0, 0)
    )
    v = lax.dynamic_update_slice(
        state["cache"]["v"], row_cache["v"], (0, slot, 0, 0, 0)
    )
    return {
        "cache": {"k": k, "v": v},
        "length": state["length"].at[slot].set(length),
        "remaining": state["remaining"].at[slot].set(remaining),
        "active": state["active"].at[slot].set(remaining > 0),
        "temperature": state["temperature"].at[slot].set(temperature),
        "last_logits": state["last_logits"].at[slot].set(last_logits[0]),
        "key": state["key"],
    }


@scope(SCOPE_PREFILL)
def _admit_rows_body(state, params, cfg: TransformerConfig, slots,
                     prompt_tokens, prompt_lengths, remaining, temperature):
    b, t0 = prompt_tokens.shape
    total_len = _scratch_len(cfg, t0, state["cache"]["k"].shape[2])
    cache = init_cache(cfg, b, total_len)
    prompt_lengths = jnp.maximum(prompt_lengths, 1)
    valid = jnp.arange(total_len)[None, :] < prompt_lengths[:, None]
    positions = jnp.broadcast_to(jnp.arange(t0)[None], (b, t0))
    logits, cache = forward_cached(
        params, prompt_tokens, cfg, cache, 0, positions, valid,
        token_valid=positions < prompt_lengths[:, None],
    )
    last = jnp.take_along_axis(
        logits, (prompt_lengths - 1)[:, None, None], axis=1
    )[:, 0]

    def rows(store, new):
        if new is None:  # a latent cache's empty side
            return None
        if new.shape[2] == store.shape[2]:
            return store.at[:, slots].set(new)
        return store.at[:, slots, :new.shape[2]].set(new)

    return {
        **state,
        "cache": {
            "k": rows(state["cache"]["k"], cache["k"]),
            "v": rows(state["cache"]["v"], cache["v"]),
        },
        "length": state["length"].at[slots].set(prompt_lengths),
        "remaining": state["remaining"].at[slots].set(remaining),
        "active": state["active"].at[slots].set(remaining > 0),
        "temperature": state["temperature"].at[slots].set(temperature),
        "last_logits": state["last_logits"].at[slots].set(last),
        "key": state["key"],
    }, last


@functools.partial(jax.jit,
                   static_argnames=("cfg", "top_k", "eos_id", "mesh"),
                   donate_argnames=("state",))
def admit_rows_and_step(state, params, cfg: TransformerConfig, slots,
                        prompt_tokens, prompt_lengths, remaining,
                        temperature, top_k: int = 0,
                        eos_id: int | None = None, mesh=None):
    """Fused admission: prefill ``[K, T0]`` prompts, scatter them into
    rows ``slots`` of the persistent state, AND run one decode step for
    every active row — a single dispatch, so the new requests' first
    token ships on the admission dispatch itself (one dispatch
    prompt→token where a prefill/insert/step pipeline makes three), and
    peer rows advance
    exactly as a separate ramp step would have advanced them. ``slots``
    may repeat indices only as bucket padding that duplicates a real
    admission verbatim (identical data per duplicate index keeps the
    scatter deterministic). ``mesh`` (static) is the serving mesh of a
    sharded decoder, for the fused step as for :func:`decode_step`.
    Returns (state, prefill last-logits [K, V], sampled token [slots],
    emitted mask [slots])."""
    state, last = _admit_rows_body(state, params, cfg, slots,
                                   prompt_tokens, prompt_lengths,
                                   remaining, temperature)
    state, tok, emit = _decode_step_body(state, params, cfg, top_k, eos_id,
                                         mesh=mesh)
    return state, last, tok, emit


# ---------------------------------------------------------------------------
# Prefix KV pool (serving/prefix_cache.py holds the host-side trie)
# ---------------------------------------------------------------------------
#
# Most production prompts share a long common prefix (system prompt,
# few-shot template); causality makes its K/V rows depend only on the
# prefix tokens themselves, so they can be computed once, parked in a
# fixed-capacity device pool, and gathered into a new request's row at
# admission — the request then prefills ONLY its suffix. The pool is
# deliberately functional (no donation): a store never invalidates the
# array an in-flight admission already captured, so host-side pinning is
# a logical-consistency guard, not a memory-safety one.


def init_prefix_pool(cfg: TransformerConfig, pool_slots: int,
                     max_prefix_len: int):
    """Device prefix pool: ``pool_slots`` rows of per-layer K/V for up to
    ``max_prefix_len`` positions, laid out like the decode cache (layer
    dim leading) so row gather/scatter is a contiguous copy."""
    shape = (cfg.cache_layers, pool_slots, max_prefix_len, cfg.n_kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


@jax.jit
def store_prefix_row(pool, pool_slot, state, row):
    """Publish decode-state row ``row``'s first ``max_prefix_len`` cache
    positions into pool row ``pool_slot`` (the publish-on-finish path:
    the prompt region of a finished request's row is its prefix). Both
    indices are traced — one executable serves every (row, slot) pair."""
    plen = pool["k"].shape[2]
    return {
        "k": pool["k"].at[:, pool_slot].set(state["cache"]["k"][:, row,
                                                                :plen]),
        "v": pool["v"].at[:, pool_slot].set(state["cache"]["v"][:, row,
                                                                :plen]),
    }


@jax.jit
def store_prefix_cache(pool, pool_slot, cache):
    """Publish a batch-1 :func:`prefill` cache into pool row ``pool_slot``
    (the prime path: preload a shared system prompt without touching the
    decode state or its RNG)."""
    plen = pool["k"].shape[2]
    return {
        "k": pool["k"].at[:, pool_slot].set(cache["k"][:, 0, :plen]),
        "v": pool["v"].at[:, pool_slot].set(cache["v"][:, 0, :plen]),
    }


@scope(SCOPE_PREFILL)
def _admit_prefix_body(state, params, cfg: TransformerConfig, slot, pool,
                       pool_slot, prefix_len, suffix_tokens, prompt_len,
                       remaining, temperature):
    total_len = state["cache"]["k"].shape[2]
    _b, s = suffix_tokens.shape  # batch 1, suffix padded to a length bucket
    cache = init_cache(cfg, 1, total_len)
    # Lay the reused prefix rows into cache positions 0..max_prefix_len.
    # Rows past prefix_len hold the donor's unrelated continuation — the
    # suffix forward overwrites positions prefix_len..prefix_len+s, and
    # ``valid`` masks everything beyond prompt_len until decode writes it.
    k0 = lax.dynamic_update_slice(
        cache["k"], pool["k"][:, pool_slot][:, None], (0, 0, 0, 0, 0))
    v0 = lax.dynamic_update_slice(
        cache["v"], pool["v"][:, pool_slot][:, None], (0, 0, 0, 0, 0))
    suffix_len = jnp.maximum(prompt_len - prefix_len, 1)
    positions = prefix_len + jnp.arange(s)[None, :]
    valid = jnp.arange(total_len)[None, :] < prompt_len
    logits, cache = forward_cached(
        params, suffix_tokens, cfg, {"k": k0, "v": v0}, prefix_len,
        positions, valid,
        token_valid=jnp.arange(s)[None, :] < suffix_len,
    )
    last = jnp.take_along_axis(
        logits, jnp.reshape(suffix_len - 1, (1, 1, 1)), axis=1
    )[:, 0]
    return {
        "cache": {
            "k": state["cache"]["k"].at[:, slot].set(cache["k"][:, 0]),
            "v": state["cache"]["v"].at[:, slot].set(cache["v"][:, 0]),
        },
        "length": state["length"].at[slot].set(prompt_len),
        "remaining": state["remaining"].at[slot].set(remaining),
        "active": state["active"].at[slot].set(remaining > 0),
        "temperature": state["temperature"].at[slot].set(temperature),
        "last_logits": state["last_logits"].at[slot].set(last[0]),
        "key": state["key"],
    }, last


@functools.partial(jax.jit,
                   static_argnames=("cfg", "top_k", "eos_id", "mesh"),
                   donate_argnames=("state",))
def admit_prefix_and_step(state, params, cfg: TransformerConfig, slot, pool,
                          pool_slot, prefix_len, suffix_tokens, prompt_len,
                          remaining, temperature, top_k: int = 0,
                          eos_id: int | None = None, mesh=None):
    """Prefix-hit admission: gather pool row ``pool_slot``'s first
    ``prefix_len`` K/V positions into decode-state row ``slot``, prefill
    ONLY the suffix (``suffix_tokens`` [1, S], padded to a length
    bucket), and run one fused decode step — the prefix-reuse twin of
    :func:`admit_rows_and_step`, still a single dispatch. ``prefix_len``
    and ``prompt_len`` are traced, so one executable per suffix bucket
    serves every cached prefix length. ``mesh`` as in
    :func:`admit_rows_and_step`. Returns (state, prefill
    last-logits [1, V], sampled token [slots], emitted mask [slots])."""
    state, last = _admit_prefix_body(state, params, cfg, slot, pool,
                                     pool_slot, prefix_len, suffix_tokens,
                                     prompt_len, remaining, temperature)
    state, tok, emit = _decode_step_body(state, params, cfg, top_k, eos_id,
                                         mesh=mesh)
    return state, last, tok, emit


@functools.partial(jax.jit, donate_argnames=("state",))
def retire_row(state, slot):
    """Host-initiated early stop (EOS, or a QoS suspension): clear
    ``active`` and park the row's write position at ``total`` so the next
    ``decode_step`` neither samples for it nor lands its cache scatter
    (out-of-bounds scatter updates are dropped — same parking the fused
    EOS path uses on device). Works on either KV layout via
    :func:`_state_kv`; ``insert_row``/admission resets ``length`` on
    readmission."""
    total = _state_kv(state)[3]
    return _zero_row_state(
        {**state,
         "active": state["active"].at[slot].set(False),
         "length": state["length"].at[slot].set(total)}, slot)


def _zero_row_state(state, slot):
    """Recurrent state has no validity mask to hide behind: a retired
    row's is zeroed (and an admission at position 0 starts from zero
    whatever it finds). A state that holds none comes back as it is."""
    if "lin_state" not in state:
        return state
    return {**state, "lin_state": tuple(s.at[slot].set(0.0)
                                        for s in state["lin_state"])}


def _state_kv(state):
    """Layout-agnostic view of a decode state's KV storage: returns
    ``(k, v, table, total)``. Dense states carry ``[L, slots, total, H,
    hd]`` caches (table None); paged states carry the block pool
    ``[L, N, Bs, H, hd]`` plus the ``[slots, max_blocks]`` block table
    (virtual ``total = max_blocks * Bs``)."""
    if "pool" in state:
        k = state["pool"]["k"]
        table = state["block_table"]
        # A mixer_types state keeps its pool head-major (see
        # init_paged_state): the block's tokens are one axis further in.
        bs = k.shape[3] if "lin_state" in state else _kv_arr(k).shape[2]
        return k, state["pool"]["v"], table, table.shape[1] * bs
    k = state["cache"]["k"]
    return k, state["cache"]["v"], None, k.shape[2]


def _with_kv(state, k, v):
    """Refresh a state's KV storage under whichever layout it carries."""
    if "pool" in state:
        return {**state, "pool": {"k": k, "v": v}}
    return {**state, "cache": {"k": k, "v": v}}


def _layer_loop(params, cfg: TransformerConfig, x, k_store, v_store, attend,
                token_valid):
    """THE layer loop of every forward over persistent K/V storage
    (:func:`_single_token_forward`, :func:`_block_forward`, and a looped
    stack's :func:`forward_cached`): a scan whose CARRY is
    ``(x, k_store, v_store)`` — the whole storage, dense cache or paged
    pool (both leaves of a quantized one) — and whose scanned inputs are
    the per-layer parameters and the layer's index into the storage.
    ``attend(h, attn_params, k_store, v_store, li)`` writes cache layer
    ``li``'s K/V into the storage in place and attends over it; it returns
    (out, k_store, v_store). The storage is never the scan's ``xs``/``ys``:
    a scan cannot alias the two, so that form holds a second whole store
    and copies it every step.

    A looped stack (``cfg.n_passes`` > 1) makes the loop a nest: passes
    outside, the same scan over the same parameters inside, pass ``u``
    writing and reading cache layers ``u * n_layers + layer``, each pass
    closed by the final norm whose output is the next one's input. One
    pass is the inner scan alone. Returns (logits [B, S, V], k_store,
    v_store, routed): ``routed`` [expert layers, E], the tokens each
    expert took, from a model with the dropless expert layer, else None.

    A latent model (``cfg.kv_lora_rank``) carries its latent store as
    ``k_store`` (``v_store`` None) and runs its leading dense layers one
    by one ahead of the scan over its expert layers."""

    def layer_fn(carry, layer_and_index):
        x, k_store, v_store = carry
        layer, li = layer_and_index
        h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
        attn, k_store, v_store = attend(h, layer["attn"], k_store, v_store,
                                        li)
        x = x + post_norm(attn, layer, "ln_attn_post", cfg)
        h = rms_norm(x, layer["ln_mlp"], eps=cfg.norm_eps)
        if cfg.kv_lora_rank:
            mlp, expert_layer = layer["mlp"], None
            if "router" in mlp:
                mlp, expert_layer = {**mlp, **experts}, li - cfg.first_k_dense
            y, routed = latent_ffn(h, mlp, cfg, token_valid, expert_layer)
            return (x + y, k_store, v_store), routed
        x = x + post_norm(_ffn(h, layer["mlp"], cfg, token_valid), layer,
                          "ln_mlp_post", cfg)
        return (x, k_store, v_store), None

    # A latent model's expert stacks stay out of the scanned inputs: each
    # layer reads its experts where they lie in the whole stacks
    # (transformer._grouped), as the attention reads the K/V store.
    scanned, experts = params["layers"], {}
    if cfg.kv_lora_rank:
        mlp = dict(scanned["mlp"])
        experts = {name: mlp.pop(name) for name in ("gate", "up", "down")}
        scanned = {**scanned, "mlp": mlp}

    def one_pass(carry, cache_layers):
        (x, k_store, v_store), routed = lax.scan(
            layer_fn, carry, (scanned, cache_layers))
        return (close_pass(x, params, cfg), k_store, v_store), routed

    carry = (x, k_store, v_store)
    # A latent model's leading dense layers, ahead of the scan over its
    # expert layers (cache layers first_k_dense and on).
    for i in range(cfg.first_k_dense):
        carry, _ = layer_fn(carry, (jax.tree.map(
            lambda w: w[i], params["dense_layers"]), i))
    layers = (jnp.arange(cfg.first_k_dense, cfg.n_layers)
              if cfg.first_k_dense else jnp.arange(cfg.n_layers))
    routed = None
    if cfg.n_passes == 1:
        (x, k_store, v_store), routed = one_pass(carry, layers)
    else:
        (x, k_store, v_store), _ = lax.scan(
            lambda c, u: (one_pass(c, u * cfg.n_layers + layers)[0], None),
            carry, jnp.arange(cfg.n_passes))
    return _head(params, x, cfg), k_store, v_store, routed


def _single_token_forward(params, cfg: TransformerConfig, k_cache0, v_cache0,
                          tok, pos_b, token_valid, table=None, fused=False,
                          mesh=None):
    """One [B, 1] forward at per-row cache positions ``pos_b`` against the
    persistent caches (shared by :func:`_decode_step_body` and the verify
    commit pass). The caches ride :func:`_layer_loop` as its carry, whole
    — written and read in place at ``[layer, ...]``, never scanned as
    ``xs``/``ys`` (that form copies the whole cache every step). With
    ``table`` the caches are the paged block pool read/written through
    the block table (``fused`` swaps the gathered read for the
    block-walking attention kernel). Returns (logits [B, V], k, v, the
    tokens each expert took or None: :func:`_layer_loop`)."""
    total = (k_cache0.shape[2] if table is None
             else table.shape[1] * _kv_arr(k_cache0).shape[2])
    cos_t, sin_t = rotary_frequencies(cfg.rope_dim, total,
                                      theta=cfg.rope_theta)
    rope_bt = (cos_t[pos_b[:, None]], sin_t[pos_b[:, None]])
    x = _embed(params, tok, cfg)[:, None]
    valid = jnp.arange(total)[None, :] <= pos_b[:, None]
    write_index = (_dense_write_index(pos_b[:, None]) if table is None
                   else None)

    def attend(h, attn, k_store, v_store, li):
        if cfg.kv_lora_rank:
            return _latent_attention(h, attn, cfg, rope_bt, k_store, li,
                                     pos_b, token_valid, write_index)
        return _ragged_attention(h, attn, cfg, rope_bt, k_store, v_store, li,
                                 pos_b, valid, token_valid, table=table,
                                 fused=fused, mesh=mesh,
                                 write_index=write_index)

    logits, k_new, v_new, routed = _layer_loop(
        params, cfg, x, k_cache0, v_cache0, attend, token_valid[:, None])
    return logits[:, 0], k_new, v_new, routed


def _decode_step_body(state, params, cfg: TransformerConfig, top_k: int,
                      eos_id: int | None, fused: bool = False, mesh=None):
    """One decode step (traceable body shared by :func:`decode_step` and
    :func:`decode_chunk`). With ``eos_id`` set, a row that samples it is
    parked ON DEVICE (active cleared, write position parked at ``total``
    like :func:`retire_row`) so a fused multi-step loop needs no host
    round-trip per token to stop at EOS. Works on either KV layout
    (:func:`_state_kv`): dense per-slot rows or the paged block pool
    (``fused`` swaps the paged read for the block-table kernel)."""
    k0, v0, table, total = _state_kv(state)
    emit = state["active"]
    key, sub = jax.random.split(state["key"])
    tok = sample_token(state["last_logits"], sub, state["temperature"], top_k)
    p_b = state["length"]
    with scope(SCOPE_DECODE):
        if cfg.mixer_types:
            logits, held = _hybrid_token_forward(params, cfg, state, tok,
                                                 p_b, emit)
            state = {**state, **held}
            k_new, v_new = state["pool"]["k"], state["pool"]["v"]
        else:
            logits, k_new, v_new, routed = _single_token_forward(
                params, cfg, k0, v0, tok, p_b, emit, table=table,
                fused=fused, mesh=mesh,
            )
        step_inc = emit.astype(jnp.int32)
        length = p_b + step_inc
        remaining = state["remaining"] - step_inc
        active = emit & (remaining > 0) & (length < total)
        if eos_id is not None:
            hit_eos = emit & (tok == eos_id)
            active = active & ~hit_eos
            # Park like retire_row: an out-of-bounds write position drops
            # the row's cache scatter on subsequent fused steps.
            length = jnp.where(hit_eos, total, length)
        new_state = {
            **state,
            "length": length,
            "remaining": remaining,
            "active": active,
            "last_logits": jnp.where(emit[:, None], logits,
                                     state["last_logits"]),
            "key": key,
        }
        if "moe_routed" in state:
            new_state["moe_routed"] = state["moe_routed"] + routed
            new_state["moe_touched"] = state["moe_touched"] + jnp.sum(
                routed > 0, axis=-1, dtype=jnp.int32)
    return _with_kv(new_state, k_new, v_new), tok, emit


@functools.partial(jax.jit,
                   static_argnames=("cfg", "top_k", "eos_id", "kv_fused",
                                    "mesh"),
                   donate_argnames=("state",))
def decode_step(state, params, cfg: TransformerConfig, top_k: int = 0,
                eos_id: int | None = None, kv_fused: bool = False,
                mesh=None):
    """One token for every active row: sample from each row's last logits,
    run the [slots, 1] forward at per-row positions, refresh the state.
    Returns (state, sampled token [slots], emitted mask [slots]) — the host
    dispatches ``token[i]`` to request ``i`` wherever ``emitted[i]``.
    ``kv_fused`` (paged states only) reads the cache through the
    block-table attention kernel instead of the gathered dense view;
    ``mesh`` (static, a tensor-parallel serving mesh) routes that fused
    read through the kernel's shard_map mesh twin, and keeps a dense
    cache's read the XLA one."""
    return _decode_step_body(state, params, cfg, top_k, eos_id, kv_fused,
                             mesh)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "steps", "top_k", "eos_id",
                                    "kv_fused", "mesh"),
                   donate_argnames=("state",))
def decode_chunk(state, params, cfg: TransformerConfig, steps: int,
                 top_k: int = 0, eos_id: int | None = None,
                 kv_fused: bool = False, mesh=None):
    """``steps`` decode steps fused into ONE device dispatch via
    ``lax.scan``: K tokens per dispatch and per host fetch instead of
    one, at the price that the host sees tokens (and can admit a new
    request) only at chunk boundaries. EOS and
    row-exhaustion are handled inside the loop on device (rows park
    exactly as :func:`retire_row` would). Returns
    (state, tokens [steps, slots], emitted [steps, slots]); the host
    flushes each request's stream once per chunk."""

    def body(s, _):
        s, tok, emit = _decode_step_body(s, params, cfg, top_k, eos_id,
                                         kv_fused, mesh)
        return s, (tok, emit)

    state, (toks, emits) = lax.scan(body, state, None, length=steps)
    return state, toks, emits


# ---------------------------------------------------------------------------
# Speculative decoding (serving/speculative.py holds the host-side proposers)
# ---------------------------------------------------------------------------
#
# Decode is memory-bandwidth-bound: every step reads the whole KV cache to
# produce ONE token. Verifying K cheap draft tokens in a single [slots, K]
# forward reads the cache once for up to K+1 tokens of progress — the
# verify is compute the prefill path already knows how to do. Greedy
# outputs are byte-identical to plain decode by construction (a draft
# token is only kept when it equals the argmax the target would have
# produced); temperature>0 rows use rejection-resampling against the
# deterministic draft proposal, which leaves the sampled distribution
# exactly the target's. A verify step is two forwards fused into ONE
# dispatch: the K-wide scoring pass plus a single-token commit pass that
# writes the first non-draft token's K/V, so the decode-state invariant
# (``length`` K/V rows live, ``last_logits`` predicts position
# ``length``) holds on exit and verify composes freely with
# ``decode_step``/``decode_chunk``/``retire_row``. Rejected draft tails
# need no explicit rollback: validity is derived from ``length`` every
# step, so not advancing past the accepted region IS the rollback.


@scope(SCOPE_ATTN)
def _span_attention(x, layer, cfg, rope_bt, k_store, v_store, li, pos_b,
                    table=None, fused=False, mesh=None, ring=None):
    """Block attention of layer ``li`` where row ``b``'s ``S`` tokens
    occupy cache slots ``pos_b[b]..pos_b[b]+S-1`` — the S-wide sibling of
    :func:`_ragged_attention` (rows at heterogeneous positions; the same
    whole storage written and read in place at ``[li, ...]``). Block
    token ``s`` attends every cache slot ``<= pos_b + s`` (its own K/V
    was just written), so causality holds within the block and over the
    row's history. Out-of-bounds writes (parked rows, cache-tail spill)
    are dropped by scatter semantics. With ``table`` the storage is the
    paged block pool, written/read through the block table; ``fused``
    swaps the gathered read for the span block-walk
    (ops/attention.py:paged_span_attention) so the dense
    ``[B, MB*Bs]`` view is never materialized — the same contract (and
    the same f32-equivalent-not-bitwise caveat) as the fused decode
    read. ``ring`` (a serving mesh with a ``sequence`` axis) routes the
    gathered span read through the context-parallel ring
    (ops/attention.py:ring_span_attention) — chunked-prefill's long-
    prompt path, same f32-equivalence caveat."""
    b, s, _d = x.shape
    q, k, v = _qkv_rope(x, layer, cfg, rope_bt)
    cols = pos_b[:, None] + jnp.arange(s)[None, :]
    k_store = _store_write(k_store, li, table, cols, k)
    v_store = _store_write(v_store, li, table, cols, v)
    if fused:
        # Span contract: token ``s`` attends positions <= pos_b + s —
        # exactly the mask below, walked block-by-block instead of
        # gathered dense.
        out = paged_span_attention(
            q, _layer_of(k_store, li), _layer_of(v_store, li), table, pos_b,
            n_kv_heads=cfg.n_kv_heads, mesh=mesh)
    else:
        k_read = _store_rows(k_store, li, table)
        v_read = _store_rows(v_store, li, table)
        if ring is not None:
            out = ring_span_attention(q, k_read, v_read, pos_b,
                                      n_kv_heads=cfg.n_kv_heads, mesh=ring)
        else:
            mask = (jnp.arange(k_read.shape[1])[None, None, :]
                    <= cols[:, :, None])
            out = _gqa_attention(q, k_read, v_read, mask[:, None, None], cfg)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim).astype(cfg.dtype)
    return out @ cast_param(layer["wo"], cfg.dtype), k_store, v_store


def _block_forward(params, cfg: TransformerConfig, k_cache0, v_cache0,
                   tokens, pos_b, token_valid, table=None, fused=False,
                   mesh=None, ring=None):
    """[B, S] forward writing K/V at per-row start positions ``pos_b`` →
    (logits [B, S, V], k, v). The verify scoring pass, the paged
    suffix-only prefill, and the draft model's catch-up feed all ride
    this, over the same storage and through the same :func:`_layer_loop`
    as the single-token step; ``fused`` routes the paged span read
    through the block-walk instead of the dense gather."""
    total = (k_cache0.shape[2] if table is None
             else table.shape[1] * _kv_arr(k_cache0).shape[2])
    _b, s = tokens.shape
    cos_t, sin_t = rotary_frequencies(cfg.head_dim, total,
                                      theta=cfg.rope_theta)
    pos = pos_b[:, None] + jnp.arange(s)[None, :]
    rope_bt = (cos_t[pos], sin_t[pos])
    x = _embed(params, tokens, cfg)

    def attend(h, attn, k_store, v_store, li):
        return _span_attention(h, attn, cfg, rope_bt, k_store, v_store, li,
                               pos_b, table=table, fused=fused, mesh=mesh,
                               ring=ring)

    return _layer_loop(params, cfg, x, k_cache0, v_cache0, attend,
                       token_valid)[:3]


def _target_probs(logits, temperature, top_k: int):
    """Processed target distribution (top-k mask + temperature floor) for
    speculative accept/resample — must match :func:`sample_token`'s
    sampling branch exactly or acceptance would test a different
    distribution than the one decode samples from. logits [..., V],
    temperature broadcastable to logits[..., 0]."""
    logits = _top_k_mask(logits, top_k)
    temp = jnp.maximum(temperature, 1e-6)[..., None]
    return jax.nn.softmax(logits / temp, axis=-1)


@scope(SCOPE_DECODE)
def _verify_step_body(state, params, cfg: TransformerConfig, draft,
                      draft_len, top_k: int, eos_id: int | None,
                      fused: bool = False, mesh=None):
    """One speculative verify: score ``draft`` [slots, K] against the
    decode state, accept each row's longest matching prefix, commit the
    first non-draft token. Returns (state, tokens [slots, K+1],
    emitted [slots, K+1]) — ``emitted`` is a per-row prefix mask over
    the emitted tokens (1..K+1 of them for active rows)."""
    k0, v0, table, total = _state_kv(state)
    slots, k_w = draft.shape
    emit0 = state["active"]
    p_b = state["length"]
    temp = state["temperature"]
    key, k_acc, k_res = jax.random.split(state["key"], 3)

    # Pass 1: ONE [slots, K] forward scores every draft position (and
    # writes the draft K/V — accepted rows keep it, rejected tails stay
    # masked out by ``length`` until overwritten). ``fused`` walks the
    # span read through the block table instead of gathering the dense
    # view — the K-wide twin of the fused decode read.
    in_draft = jnp.arange(k_w)[None, :] < draft_len[:, None]
    block_logits, k1, v1 = _block_forward(
        params, cfg, k0, v0, draft, p_b,
        token_valid=emit0[:, None] & in_draft, table=table, fused=fused,
        mesh=mesh,
    )
    # prev_logits[:, i] predicts draft position i: last_logits for i=0,
    # the scoring pass's own outputs shifted by one after that.
    prev_logits = jnp.concatenate(
        [state["last_logits"][:, None], block_logits[:, : k_w - 1]], axis=1
    )
    greedy_ok = draft == jnp.argmax(prev_logits, axis=-1)
    probs = _target_probs(prev_logits, temp[:, None], top_k)
    p_draft = jnp.take_along_axis(probs, draft[..., None], axis=-1)[..., 0]
    # Deterministic proposer => q is a point mass: accept w.p. p(d).
    sampled_ok = jax.random.uniform(k_acc, (slots, k_w)) < p_draft
    ok = jnp.where((temp <= 0.0)[:, None], greedy_ok, sampled_ok) & in_draft
    acc = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    n = acc.sum(axis=1)
    # Emission is n accepted drafts + 1 committed token, capped so the row
    # never overruns its budget or its cache (the cap only ever DROPS
    # accepted drafts — the capped position was accepted, so emitting the
    # draft token there stays distribution-exact).
    n_eff = jnp.minimum(n, jnp.maximum(state["remaining"] - 1, 0))
    n_eff = jnp.minimum(n_eff, jnp.maximum(total - 1 - p_b, 0))

    # Commit token: target sample at position n_eff. On a true rejection
    # the rejected draft id is masked out first — rejection-resampling
    # from the residual of a point-mass proposal, which keeps the overall
    # per-position distribution exactly the target's.
    all_logits = jnp.concatenate(
        [prev_logits, block_logits[:, k_w - 1:]], axis=1
    )
    commit_logits = jnp.take_along_axis(
        all_logits, n_eff[:, None, None], axis=1
    )[:, 0]
    d_at = jnp.take_along_axis(
        draft, jnp.minimum(n_eff, k_w - 1)[:, None], axis=1
    )[:, 0]
    rejected = (n_eff == n) & (n_eff < draft_len)
    # Top-k BEFORE the rejection mask: the residual must stay inside the
    # target's top-k support (masking first and re-thresholding after
    # would let the k+1-th token leak into the resample).
    res_logits = jnp.where(
        rejected[:, None]
        & (jnp.arange(cfg.vocab_size)[None, :] == d_at[:, None]),
        _NEG_INF, _top_k_mask(commit_logits, top_k),
    )
    commit = sample_token(res_logits, k_res, temp, top_k=0)
    commit = jnp.where(n_eff < n, d_at, commit)

    idx = jnp.arange(k_w + 1)[None, :]
    draft_pad = jnp.concatenate(
        [draft, jnp.zeros((slots, 1), jnp.int32)], axis=1
    )
    out = jnp.where(idx < n_eff[:, None], draft_pad, commit[:, None])
    emitted = emit0[:, None] & (idx <= n_eff[:, None])
    hit_eos = jnp.zeros((slots,), bool)
    if eos_id is not None:
        is_eos = (out == eos_id) & emitted
        eos_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) \
            - is_eos.astype(jnp.int32)
        emitted = emitted & (eos_before == 0)  # keep the EOS, drop its tail
        hit_eos = ((out == eos_id) & emitted).any(axis=1)
    m = emitted.sum(axis=1).astype(jnp.int32)

    # Pass 2 (same dispatch): write the commit token's K/V at its row
    # position and refresh last_logits — restores the decode invariant so
    # the next step (plain or verify) continues seamlessly. Rows parked
    # by EOS above still run the pass; their write lands inside the row
    # but the row's length is parked at ``total`` so it is never read.
    commit_pos = p_b + n_eff
    logits2, k2, v2, _ = _single_token_forward(
        params, cfg, k1, v1, commit, commit_pos, emit0, table=table,
        fused=fused, mesh=mesh,
    )

    length = p_b + m
    remaining = state["remaining"] - m
    active = emit0 & (remaining > 0) & (length < total) & ~hit_eos
    length = jnp.where(hit_eos, total, length)
    new_state = {
        **state,
        "length": length,
        "remaining": remaining,
        "active": active,
        "last_logits": jnp.where(emit0[:, None], logits2,
                                 state["last_logits"]),
        "key": key,
    }
    return _with_kv(new_state, k2, v2), out, emitted


@functools.partial(jax.jit,
                   static_argnames=("cfg", "top_k", "eos_id", "kv_fused",
                                    "mesh"),
                   donate_argnames=("state",))
def verify_step(state, params, cfg: TransformerConfig, draft, draft_len,
                top_k: int = 0, eos_id: int | None = None,
                kv_fused: bool = False, mesh=None):
    """Score ``draft`` [slots, K] tokens against the decode-state KV cache
    in ONE fused dispatch and emit each row's longest accepted prefix plus
    one committed target token (1..K+1 tokens of progress per row).
    Greedy rows are byte-identical to plain :func:`decode_step` chains;
    temperature>0 rows rejection-resample so the sampled distribution is
    unchanged. EOS parks rows on device exactly like
    :func:`_decode_step_body`. Returns (state, tokens [slots, K+1],
    emitted [slots, K+1])."""
    return _verify_step_body(state, params, cfg, draft, draft_len, top_k,
                             eos_id, kv_fused, mesh)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "top_k", "eos_id", "kv_fused",
                                    "mesh"),
                   donate_argnames=("state",))
def verify_chunk(state, params, cfg: TransformerConfig, drafts, draft_lens,
                 top_k: int = 0, eos_id: int | None = None,
                 kv_fused: bool = False, mesh=None):
    """``steps`` verify steps fused into ONE dispatch via ``lax.scan`` —
    the speculative twin of :func:`decode_chunk`, so a chunk of K-token
    verifies is still one dispatch and one host fetch. ``drafts``
    [steps, slots, K] holds each step's proposals (later slices are
    chain continuations that simply fail verification after an early
    rejection — correctness never depends on the proposer being right).
    Returns (state, tokens [steps, slots, K+1], emitted likewise)."""

    def body(s, xs):
        draft, dlen = xs
        s, out, emitted = _verify_step_body(s, params, cfg, draft, dlen,
                                            top_k, eos_id, kv_fused, mesh)
        return s, (out, emitted)

    state, (outs, emits) = lax.scan(body, state, (drafts, draft_lens))
    return state, outs, emits


@functools.partial(jax.jit, static_argnames=("cfg", "steps"),
                   donate_argnames=("state",))
def extend_and_propose(state, params, cfg: TransformerConfig, feed,
                       feed_pos, feed_len, steps: int):
    """Draft-model helper: force-feed each row's newly committed target
    tokens (``feed`` [slots, S], ``feed_len`` real, starting at cache
    position ``feed_pos``) into the DRAFT decode state, then greedily
    decode ``steps`` proposal tokens per row — one dispatch total. The
    proposal steps advance the draft state past the confirmed region;
    the next call's feed (at host-tracked confirmed positions) overwrites
    whatever the target rejected, so no rollback pass is needed. Rows
    with ``feed_pos`` at the cache end are parked (their writes drop).
    Returns (state, proposals [slots, steps])."""
    in_feed = jnp.arange(feed.shape[1])[None, :] < feed_len[:, None]
    block_logits, k1, v1 = _block_forward(
        params, cfg, state["cache"]["k"], state["cache"]["v"], feed,
        feed_pos, token_valid=in_feed,
    )
    last = jnp.take_along_axis(
        block_logits, jnp.maximum(feed_len - 1, 0)[:, None, None], axis=1
    )[:, 0]
    live = feed_len > 0
    state = {
        "cache": {"k": k1, "v": v1},
        "length": feed_pos + feed_len,
        # Proposal budget only — the draft state's remaining/active are
        # reset from the host's feed every round.
        "remaining": jnp.where(live, steps + 1, 0).astype(jnp.int32),
        "active": live,
        "temperature": jnp.zeros_like(state["temperature"]),
        "last_logits": jnp.where(live[:, None], last,
                                 state["last_logits"]),
        "key": state["key"],
    }

    def body(s, _):
        s, tok, _emit = _decode_step_body(s, params, cfg, 0, None)
        return s, tok

    state, toks = lax.scan(body, state, None, length=steps)
    return state, toks.T  # [slots, steps]


# ---------------------------------------------------------------------------
# Paged KV cache (serving/kv_allocator.py holds the host-side allocator)
# ---------------------------------------------------------------------------
#
# The dense layout above reserves ``total_len`` K/V positions per decode
# slot — every admitted request pays worst-case HBM no matter its actual
# prompt or budget. The paged layout stores K/V in a pool of fixed-size
# blocks and maps each slot's virtual positions through a per-slot block
# table: slot ``b``'s position ``p`` lives at block
# ``table[b, p // Bs]``, offset ``p % Bs``. Concurrency is then bounded
# by TOKENS RESIDENT (blocks in use), not by ``slots * total_len``, and
# a prefix-cache hit shares the donor's full blocks by reference
# (refcounts in the host allocator) with zero device copies — only a
# partially-filled tail block is copy-on-write'd.
#
# Attention reads gather the row at block granularity and the math,
# masks, and widths are kept identical to the dense path (masked junk
# contributes exact zeros), so greedy outputs are byte-identical between
# layouts; ``decode_step`` / ``decode_chunk`` / ``verify_step`` /
# ``verify_chunk`` accept either state via :func:`_state_kv`. Table
# entries are initialised to ``num_blocks`` (an out-of-range sentinel):
# writes through unallocated entries are dropped by scatter semantics
# and gathers clamp into junk the validity mask already excludes.


def init_paged_state(cfg: TransformerConfig, slots: int, num_blocks: int,
                     block_size: int, max_blocks_per_seq: int, seed: int = 0,
                     kv_dtype: str = "fp"):
    """Paged server decode state: a device block pool
    ``[cache layers, num_blocks, block_size, Hkv, hd]`` shared by all
    slots (``cfg.cache_layers``: a looped stack's block holds K/V of
    every pass) plus a
    per-slot block table. Virtual row width is
    ``max_blocks_per_seq * block_size`` (the dense ``total_len``).

    ``kv_dtype="int8"`` stores the pool quantized: int8 payload plus one
    f32 abs-max scale per (layer, position, kv head) riding a parallel
    scale pool indexed by the SAME block ids — so the host allocator's
    share/refcount/CoW bookkeeping covers payload and scales in one
    move, and resident K/V costs ~``head_dim + 4`` bytes per head
    instead of ``head_dim * fp_bytes``."""
    if cfg.mixer_types:
        return _init_hybrid_state(cfg, slots, num_blocks, block_size,
                                  max_blocks_per_seq, seed, kv_dtype)
    if cfg.kv_lora_rank:
        raise ValueError(
            "latent attention: the block pool holds K and V a head; a "
            "latent cache serves on kv_layout='dense'")
    shape = (cfg.cache_layers, num_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    if kv_dtype == "int8":
        def _pool():
            return {"q": jnp.zeros(shape, jnp.int8),
                    "scale": jnp.zeros(shape[:-1], jnp.float32)}
        pool = {"k": _pool(), "v": _pool()}
    elif kv_dtype in ("", "fp"):
        pool = {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}
    else:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    return {
        "pool": pool,
        "block_table": jnp.full((slots, max_blocks_per_seq), num_blocks,
                                jnp.int32),
        "length": jnp.zeros((slots,), jnp.int32),
        "remaining": jnp.zeros((slots,), jnp.int32),
        "active": jnp.zeros((slots,), bool),
        "temperature": jnp.zeros((slots,), jnp.float32),
        "last_logits": jnp.zeros((slots, cfg.vocab_size), jnp.float32),
        "key": jax.random.PRNGKey(seed),
    }


# ---------------------------------------------------------------------------
# Hybrid decoders (cfg.mixer_types): recurrent state and compressed keys
# beside a K/V pool that only the sparse layers have
# ---------------------------------------------------------------------------
#
# Paged layout only. The state holds, beside the scalars every state has:
#   pool       {"k", "v"}: [La, N, Hkv, Bs, hd] for the La sparse layers,
#              HEAD-MAJOR inside a block (the other models' pools are
#              [L, N, Bs, Hkv, hd]): the selection is per KV head, so a
#              (block, head) tile is what a read gathers, and here it is
#              contiguous. Bs is the selection's block size.
#   lin_state  one [slots, H, hd, hd] float32 array per lightning layer
#   ckeys      one [slots, Hkv, W, hd] array per sparse layer: the mean key
#              of every window of kernel tokens, stride apart (the
#              selection's index). Window j is written when the row's
#              token stride*j + kernel - 1 is, and read by position, so a
#              reused slot needs no clearing.
# The layer loop is unrolled (the kinds' trees differ); every pool access
# names its layer in the index, so each is one gather or scatter on the
# whole donated array and no layer is ever sliced out and put back.


def _init_hybrid_state(cfg: TransformerConfig, slots: int, num_blocks: int,
                       block_size: int, max_blocks_per_seq: int, seed: int,
                       kv_dtype: str):
    spec = cfg.sparse_spec
    if kv_dtype not in ("", "fp"):
        raise ValueError(
            f"mixer_types: int8 KV (kv_dtype {kv_dtype!r}) is not "
            "supported: the block selection reads keys at the model dtype")
    if block_size != spec.block:
        raise ValueError(
            f"mixer_types: kv_block_size {block_size} must equal the "
            f"sparse layers' block size {spec.block} (a selected block is "
            "a pool block)")
    n_sparse = len(cfg.layers_of(MIXER_SPARSE))
    n_lightning = len(cfg.layers_of(MIXER_LIGHTNING))
    hd = cfg.head_dim
    shape = (n_sparse, num_blocks, cfg.n_kv_heads, block_size, hd)
    windows = spec.n_windows(max_blocks_per_seq * block_size)
    return {
        "pool": {"k": jnp.zeros(shape, cfg.dtype),
                 "v": jnp.zeros(shape, cfg.dtype)},
        "lin_state": tuple(
            jnp.zeros((slots, cfg.n_heads, hd, hd), jnp.float32)
            for _ in range(n_lightning)),
        "ckeys": tuple(
            jnp.zeros((slots, cfg.n_kv_heads, windows, hd), cfg.dtype)
            for _ in range(n_sparse)),
        "block_table": jnp.full((slots, max_blocks_per_seq), num_blocks,
                                jnp.int32),
        "length": jnp.zeros((slots,), jnp.int32),
        "remaining": jnp.zeros((slots,), jnp.int32),
        "active": jnp.zeros((slots,), bool),
        "temperature": jnp.zeros((slots,), jnp.float32),
        "last_logits": jnp.zeros((slots, cfg.vocab_size), jnp.float32),
        "key": jax.random.PRNGKey(seed),
    }


def _hm_blocks(pool, table, cols):
    """Physical block of each virtual position ``cols`` [B, S] through
    ``table`` [B, MB]; out-of-row positions and sentinel entries resolve
    past the pool (a scatter there is dropped, a gather clamps)."""
    n, bs = pool.shape[1], pool.shape[3]
    mb = table.shape[1]
    blk = jnp.take_along_axis(table, jnp.clip(cols // bs, 0, mb - 1), axis=1)
    return jnp.where((cols >= 0) & (cols < mb * bs), blk, n)


def _hm_write(pool, layer: int, table, cols, vals):
    """Scatter ``vals`` [B, S, Hkv, hd] at virtual positions ``cols``
    [B, S] of sparse layer ``layer``."""
    heads = jnp.arange(pool.shape[2])[None, None, :]
    return pool.at[layer, _hm_blocks(pool, table, cols)[:, :, None], heads,
                   (cols % pool.shape[3])[:, :, None]].set(vals)


def _hm_read(pool, layer: int, table, cols):
    """Gather positions ``cols`` [B, S] → [B, Hkv, S, hd]."""
    heads = jnp.arange(pool.shape[2])[None, :, None]
    return pool[layer, _hm_blocks(pool, table, cols)[:, None, :], heads,
                (cols % pool.shape[3])[:, None, :]]


def _hm_row(pool, layer: int, table):
    """A layer's whole virtual rows through ``table`` [B, MB] →
    [B, Hkv, MB*Bs, hd]."""
    b, mb = table.shape
    _, _, h, bs, hd = pool.shape
    return pool[layer, table].transpose(0, 2, 1, 3, 4).reshape(
        b, h, mb * bs, hd)


@scope(SCOPE_ATTN)
def _sparse_token(x, mixer, cfg: TransformerConfig, pool_k, pool_v,
                  layer: int, table, ckeys, pos_b, live):
    """A sparse layer for one token a row at positions ``pos_b`` [B]:
    write its K/V, complete the compressed key its position completes,
    select, attend the selected blocks where they lie in the pool (the
    whole pools go to the op: no layer is sliced out). Returns
    (out [B, 1, D], pool_k, pool_v, ckeys)."""
    spec = cfg.sparse_spec
    b = x.shape[0]
    hkv, group = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q, k, v = mixer_qkv(x, mixer, cfg, hkv)
    pool_k = _hm_write(pool_k, layer, table, pos_b[:, None], k)
    pool_v = _hm_write(pool_v, layer, table, pos_b[:, None], v)
    qg = q[:, 0].reshape(b, hkv, group, cfg.head_dim)
    with scope(SCOPE_SPARSE_SELECT):
        n = pos_b + 1
        done = live & (n >= spec.kernel) & (
            (n - spec.kernel) % spec.stride == 0)
        window = jnp.where(done, (n - spec.kernel) // spec.stride,
                           ckeys.shape[2])  # past the end: dropped
        tail = pos_b[:, None] - (spec.kernel - 1) + jnp.arange(spec.kernel)
        newest = compress_last(_hm_read(pool_k, layer, table, tail))
        ckeys = ckeys.at[jnp.arange(b), :, window].set(newest)
        idx, ok = select_blocks(qg[:, :, :, None], ckeys, pos_b[:, None],
                                table.shape[1], spec)
        idx, ok = idx[:, :, 0], ok[:, :, 0]
    with scope(SCOPE_SPARSE_ATTN):
        o = sparse_decode_attention(qg, pool_k, pool_v, layer, table, idx,
                                    ok, pos_b, spec)
    return mixer_out(o.reshape(b, 1, -1), x, mixer, cfg), pool_k, pool_v, ckeys


def _hybrid_token_forward(params, cfg: TransformerConfig, state, tok, pos_b,
                          live):
    """One [B, 1] forward of a ``mixer_types`` model at per-row positions
    ``pos_b`` against the whole state. Rows that are not ``live`` (parked,
    or mid-way through a chunked admission) change nothing they hold.
    Returns (logits [B, V], the state entries it refreshed)."""
    table = state["block_table"]
    pool_k, pool_v = state["pool"]["k"], state["pool"]["v"]
    total = table.shape[1] * pool_k.shape[3]
    rope = rotary_frequencies(cfg.head_dim, total, theta=cfg.rope_theta)
    positions = pos_b[:, None]
    lin, ckeys = list(state["lin_state"]), list(state["ckeys"])
    x = _embed(params, tok, cfg)[:, None] * cfg.embed_scale
    li = si = 0
    for layer, kind in zip(params["layers"], cfg.mixer_types):
        h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
        if kind == MIXER_LIGHTNING:
            mixed, lin[li] = lightning_token(h, layer["mixer"], cfg, rope,
                                             positions, lin[li], live)
            li += 1
        else:
            mixed, pool_k, pool_v, ckeys[si] = _sparse_token(
                h, layer["mixer"], cfg, pool_k, pool_v, si, table,
                ckeys[si], pos_b, live)
            si += 1
        x = x + cfg.residual_scale * mixed
        h = rms_norm(x, layer["ln_mlp"], eps=cfg.norm_eps)
        x = x + cfg.residual_scale * _ffn(h, layer["mlp"], cfg, None)
    logits = _head(params, final_hidden(x, params, cfg), cfg)[:, 0]
    return logits, {"pool": {"k": pool_k, "v": pool_v},
                    "lin_state": tuple(lin), "ckeys": tuple(ckeys)}


def _hybrid_block_forward(params, cfg: TransformerConfig, state, slots,
                          tokens, pos_b, n_tok):
    """[B, S] forward of rows ``slots`` [B] whose tokens sit at positions
    ``pos_b[b]..``, the first ``n_tok[b]`` of them real: every admission
    of a ``mixer_types`` model, whole prompts (``pos_b`` 0) and the chunks
    of a long one alike. A row at position 0 starts from a zero recurrent
    state whatever its slot held; a later chunk carries on from what the
    chunk before it left. The head runs on each row's last real position
    only (and not at all where the caller drops the logits: an interior
    chunk's module holds no head). Returns (logits [B, V], the state
    entries it refreshed)."""
    spec = cfg.sparse_spec
    table = state["block_table"][slots]
    pool_k, pool_v = state["pool"]["k"], state["pool"]["v"]
    total = table.shape[1] * pool_k.shape[3]
    _b, s = tokens.shape
    rope = rotary_frequencies(cfg.head_dim, total, theta=cfg.rope_theta)
    positions = pos_b[:, None] + jnp.arange(s)[None, :]
    fresh = (pos_b == 0)[:, None, None, None]
    lin, ckeys = list(state["lin_state"]), list(state["ckeys"])
    x = _embed(params, tokens, cfg) * cfg.embed_scale
    li = si = 0
    for layer, kind in zip(params["layers"], cfg.mixer_types):
        h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
        if kind == MIXER_LIGHTNING:
            before = jnp.where(fresh, 0.0, lin[li][slots])
            mixed, after = lightning_span(h, layer["mixer"], cfg, rope,
                                          positions, before, n_tok)
            lin[li] = lin[li].at[slots].set(after)
            li += 1
        else:
            with scope(SCOPE_ATTN):
                q, k, v = mixer_qkv(h, layer["mixer"], cfg, cfg.n_kv_heads)
                pool_k = _hm_write(pool_k, si, table, positions, k)
                pool_v = _hm_write(pool_v, si, table, positions, v)
                k_row = _hm_row(pool_k, si, table)
                with scope(SCOPE_SPARSE_SELECT):
                    windows = compress_keys(k_row, spec)
                    ckeys[si] = ckeys[si].at[slots].set(windows)
                mixed = sparse_span(q, positions, k_row,
                                    _hm_row(pool_v, si, table), windows, h,
                                    layer["mixer"], cfg)
            si += 1
        x = x + cfg.residual_scale * mixed
        h = rms_norm(x, layer["ln_mlp"], eps=cfg.norm_eps)
        x = x + cfg.residual_scale * _ffn(h, layer["mlp"], cfg, None)
    last = jnp.take_along_axis(
        x, (jnp.maximum(n_tok, 1) - 1)[:, None, None], axis=1)
    return (_head(params, final_hidden(last, params, cfg), cfg)[:, 0],
            {"pool": {"k": pool_k, "v": pool_v},
             "lin_state": tuple(lin), "ckeys": tuple(ckeys)})


def _admitted(state, held, slots, lengths, remaining, temperature, last):
    """``state`` with the rows ``slots`` admitted: what their forward
    refreshed (``held``: the pool, and whatever else a row holds) and the
    rows' scalars."""
    return {
        **state, **held,
        "length": state["length"].at[slots].set(lengths),
        "remaining": state["remaining"].at[slots].set(remaining),
        "active": state["active"].at[slots].set(remaining > 0),
        "temperature": state["temperature"].at[slots].set(temperature),
        "last_logits": state["last_logits"].at[slots].set(last),
    }


@scope(SCOPE_PREFILL)
def _paged_admit_rows_body(state, params, cfg: TransformerConfig, slots,
                           prompt_tokens, prompt_lengths, remaining,
                           temperature):
    """Prefill a round's admissions into a scratch dense cache (the exact
    dense-path math, so logits are byte-identical), then scatter each
    row's K/V into the pool blocks the host allocated for its slot
    (``state["block_table"][slots]``; sentinel entries drop their
    writes)."""
    if cfg.mixer_types:
        prompt_lengths = jnp.maximum(prompt_lengths, 1)
        last, held = _hybrid_block_forward(
            params, cfg, state, slots, prompt_tokens,
            jnp.zeros_like(prompt_lengths), prompt_lengths)
        return _admitted(state, held, slots, prompt_lengths, remaining,
                         temperature, last), last
    pool_k, pool_v = state["pool"]["k"], state["pool"]["v"]
    bs = _kv_arr(pool_k).shape[2]
    b, t0 = prompt_tokens.shape
    total = _scratch_len(cfg, t0, state["block_table"].shape[1] * bs, bs)
    mb = total // bs
    cache = init_cache(cfg, b, total)
    prompt_lengths = jnp.maximum(prompt_lengths, 1)
    valid = jnp.arange(total)[None, :] < prompt_lengths[:, None]
    positions = jnp.broadcast_to(jnp.arange(t0)[None], (b, t0))
    logits, cache = forward_cached(
        params, prompt_tokens, cfg, cache, 0, positions, valid,
        token_valid=positions < prompt_lengths[:, None],
    )
    last = jnp.take_along_axis(
        logits, (prompt_lengths - 1)[:, None, None], axis=1
    )[:, 0]
    rows_tbl = state["block_table"][slots]  # [b, mb]
    if mb < rows_tbl.shape[1]:
        rows_tbl = rows_tbl[:, :mb]
    upd_k = cache["k"].reshape(cfg.cache_layers, b, mb, bs, cfg.n_kv_heads,
                               cfg.head_dim)
    upd_v = cache["v"].reshape(cfg.cache_layers, b, mb, bs, cfg.n_kv_heads,
                               cfg.head_dim)

    def _scatter(pool, upd):
        # Quantized pools quantize at this scatter, exactly like the
        # per-token decode write — payload and scales land together.
        if isinstance(pool, dict):
            qd = _quantize_kv(upd)
            return {"q": pool["q"].at[:, rows_tbl].set(qd["q"]),
                    "scale": pool["scale"].at[:, rows_tbl].set(qd["scale"])}
        return pool.at[:, rows_tbl].set(upd)

    held = {"pool": {"k": _scatter(pool_k, upd_k),
                     "v": _scatter(pool_v, upd_v)}}
    return _admitted(state, held, slots, prompt_lengths, remaining,
                     temperature, last), last


@functools.partial(jax.jit,
                   static_argnames=("cfg", "top_k", "eos_id", "kv_fused",
                                    "mesh"),
                   donate_argnames=("state",))
def paged_admit_rows_and_step(state, params, cfg: TransformerConfig, slots,
                              prompt_tokens, prompt_lengths, remaining,
                              temperature, top_k: int = 0,
                              eos_id: int | None = None,
                              kv_fused: bool = False, mesh=None):
    """Paged twin of :func:`admit_rows_and_step`: prefill ``[K, T0]``
    prompts, scatter them into the slots' allocated pool blocks, AND run
    one fused decode step — still a single dispatch. The host must have
    written each admitted slot's block table row before the call."""
    state, last = _paged_admit_rows_body(state, params, cfg, slots,
                                         prompt_tokens, prompt_lengths,
                                         remaining, temperature)
    state, tok, emit = _decode_step_body(state, params, cfg, top_k, eos_id,
                                         kv_fused, mesh)
    return state, last, tok, emit


@scope(SCOPE_PREFILL)
def _paged_admit_prefix_body(state, params, cfg: TransformerConfig, slot,
                             prefix_len, suffix_tokens, prompt_len,
                             remaining, temperature, fused=False,
                             mesh=None, ring=None):
    """Suffix-only prefill through the slot's block table: the leading
    ``prefix_len`` positions are already backed by shared (and possibly
    one CoW'd) blocks, so the forward reads them in place — ZERO
    device-side copies of the reused prefix — and writes only the
    suffix K/V into the slot's owned blocks. ``fused`` block-walks the
    span read too, so a fused deployment never materializes the dense
    row even at admission."""
    if cfg.mixer_types:
        # The last chunk of a chunked admission (a prefix hit is refused
        # at construction): the row's state and compressed keys carry on
        # from the chunks before it.
        last, held = _hybrid_block_forward(
            params, cfg, state, jnp.reshape(slot, (1,)), suffix_tokens,
            jnp.reshape(prefix_len, (1,)),
            jnp.reshape(jnp.maximum(prompt_len - prefix_len, 1), (1,)))
        return _admitted(state, held, slot, prompt_len, remaining,
                         temperature, last[0]), last
    table_row = state["block_table"][slot][None]  # [1, mb]
    _b, s = suffix_tokens.shape
    suffix_len = jnp.maximum(prompt_len - prefix_len, 1)
    logits, pool_k, pool_v = _block_forward(
        params, cfg, state["pool"]["k"], state["pool"]["v"], suffix_tokens,
        jnp.reshape(prefix_len, (1,)),
        token_valid=jnp.arange(s)[None, :] < suffix_len, table=table_row,
        fused=fused, mesh=mesh, ring=ring,
    )
    last = jnp.take_along_axis(
        logits, jnp.reshape(suffix_len - 1, (1, 1, 1)), axis=1
    )[:, 0]
    return {
        **state,
        "pool": {"k": pool_k, "v": pool_v},
        "length": state["length"].at[slot].set(prompt_len),
        "remaining": state["remaining"].at[slot].set(remaining),
        "active": state["active"].at[slot].set(remaining > 0),
        "temperature": state["temperature"].at[slot].set(temperature),
        "last_logits": state["last_logits"].at[slot].set(last[0]),
    }, last


@functools.partial(jax.jit,
                   static_argnames=("cfg", "top_k", "eos_id", "kv_fused",
                                    "mesh", "ring"),
                   donate_argnames=("state",))
def paged_admit_prefix_and_step(state, params, cfg: TransformerConfig, slot,
                                prefix_len, suffix_tokens, prompt_len,
                                remaining, temperature, top_k: int = 0,
                                eos_id: int | None = None,
                                kv_fused: bool = False, mesh=None,
                                ring=None):
    """Paged twin of :func:`admit_prefix_and_step` — except the reused
    prefix is never gathered or copied: the host mapped the donor's full
    blocks into ``slot``'s table (refcount-shared) and CoW'd at most the
    one partially-filled tail block, so this dispatch only prefills the
    suffix and takes the fused decode step. ``ring`` routes the span
    read through the context-parallel ring — the final chunk of a
    chunked long admission rides this so its attention over the whole
    already-scattered prompt is sequence-sharded too."""
    state, last = _paged_admit_prefix_body(state, params, cfg, slot,
                                           prefix_len, suffix_tokens,
                                           prompt_len, remaining,
                                           temperature, kv_fused, mesh,
                                           ring)
    state, tok, emit = _decode_step_body(state, params, cfg, top_k, eos_id,
                                         kv_fused, mesh)
    return state, last, tok, emit


@functools.partial(jax.jit,
                   static_argnames=("cfg", "kv_fused", "mesh", "ring"),
                   donate_argnames=("state",))
@scope(SCOPE_PREFILL)
def paged_prefill_chunk(state, params, cfg: TransformerConfig, slot, pos,
                        chunk_tokens, chunk_len, kv_fused: bool = False,
                        mesh=None, ring=None):
    """One bounded chunk of a long admission: forward ``chunk_tokens``
    ([1, S], right-padded to ``chunk_len`` real tokens) at virtual
    positions ``pos..pos+chunk_len-1`` of ``slot``'s row, writing K/V
    through the slot's block table. Each chunk's attention spans every
    previously-scattered position (the span mask admits ``<= pos + s``),
    so a chain of chunks reproduces the monolithic prefill's K/V
    byte-for-byte — chunking changes the dispatch schedule, not the
    math. The row is left PARKED (``length`` at the table horizon,
    ``active`` False): interleaved decode dispatches between chunks see
    an out-of-range position, so their unconditional scatters drop and
    their masks never admit the half-built row (the same discipline as
    :func:`retire_row`). The FINAL chunk must go through
    :func:`paged_admit_prefix_and_step` with ``prefix_len`` = tokens
    already chunked in — that activates the row, sets
    length/remaining/last_logits, and takes the fused first decode step.
    Consumes no RNG, so chunked sampling streams match monolithic ones.
    Pad positions beyond ``chunk_len`` write junk K/V exactly like the
    admit paths' padded suffixes — the next chunk (or decode) overwrites
    them before any mask admits them. ``ring`` sequence-shards the span
    read (context-parallel chunk prefill)."""
    if cfg.mixer_types:
        _last, held = _hybrid_block_forward(
            params, cfg, state, jnp.reshape(slot, (1,)), chunk_tokens,
            jnp.reshape(pos, (1,)), jnp.reshape(chunk_len, (1,)))
        total = _state_kv(state)[3]
        return {
            **state, **held,
            "length": state["length"].at[slot].set(total),
            "active": state["active"].at[slot].set(False),
        }
    table_row = state["block_table"][slot][None]  # [1, mb]
    _b, s = chunk_tokens.shape
    _logits, pool_k, pool_v = _block_forward(
        params, cfg, state["pool"]["k"], state["pool"]["v"], chunk_tokens,
        jnp.reshape(pos, (1,)),
        token_valid=jnp.arange(s)[None, :] < chunk_len, table=table_row,
        fused=kv_fused, mesh=mesh, ring=ring,
    )
    total = state["block_table"].shape[1] * _kv_arr(pool_k).shape[2]
    return {
        **state,
        "pool": {"k": pool_k, "v": pool_v},
        "length": state["length"].at[slot].set(total),
        "active": state["active"].at[slot].set(False),
    }


@functools.partial(jax.jit, donate_argnames=("pool",))
def store_blocks(pool, block_ids, cache):
    """Scatter a batch-1 :func:`prefill` cache into pool blocks
    ``block_ids`` ([nblk]; sentinel entries drop) — the paged prime path
    (preload a shared system prompt without touching the decode RNG).
    Quantized pools quantize here, so primed blocks carry their scales."""
    arr = _kv_arr(pool["k"])
    n_layers, bs = arr.shape[0], arr.shape[2]
    nblk = block_ids.shape[0]
    tail = arr.shape[3:]

    def _store(dst, vals):
        vals = vals[:, 0, : nblk * bs].reshape(n_layers, nblk, bs, *tail)
        if isinstance(dst, dict):
            qd = _quantize_kv(vals)
            return {"q": dst["q"].at[:, block_ids].set(qd["q"]),
                    "scale": dst["scale"].at[:, block_ids].set(qd["scale"])}
        return dst.at[:, block_ids].set(vals)

    return {"k": _store(pool["k"], cache["k"]),
            "v": _store(pool["v"], cache["v"])}


@jax.jit
def export_blocks(pool, block_ids):
    """Gather pool blocks ``block_ids`` ([nblk]) into a standalone
    payload — the device half of the prefill→decode KV handoff. Fp
    pools yield ``{"k": [L, nblk, Bs, H, hd], "v": ...}``; quantized
    pools yield the int8 codes AND the per-(position, head) scales
    (``{"q", "scale"}`` per side), so the payload is the pool content
    verbatim: an importer lands bit-identical values without ever
    re-quantizing. Pure gather — the donor pool is untouched, so an
    export never invalidates blocks in-flight readers share."""
    def _take(kv):
        if isinstance(kv, dict):
            return {"q": kv["q"][:, block_ids],
                    "scale": kv["scale"][:, block_ids]}
        return kv[:, block_ids]

    return {"k": _take(pool["k"]), "v": _take(pool["v"])}


@functools.partial(jax.jit, donate_argnames=("pool",))
def import_blocks(pool, block_ids, payload):
    """Scatter an :func:`export_blocks` payload into pool blocks
    ``block_ids`` — the receiving half of the KV handoff, the
    cross-replica twin of :func:`store_blocks` (which quantizes a fresh
    fp prefill; this path copies codes + scales verbatim, so a
    quantized handoff is exact by construction, never a second
    quantization). Layouts must match: an fp payload into an fp pool,
    a quantized payload into a quantized pool."""
    def _put(dst, vals):
        if isinstance(dst, dict):
            return {"q": dst["q"].at[:, block_ids].set(vals["q"]),
                    "scale": dst["scale"].at[:, block_ids].set(
                        vals["scale"])}
        return dst.at[:, block_ids].set(vals)

    return {"k": _put(pool["k"], payload["k"]),
            "v": _put(pool["v"], payload["v"])}


@functools.partial(jax.jit, donate_argnames=("pool",))
def copy_block(pool, dst, src):
    """Copy one block's K/V across the pool — the copy-on-write for a
    partially-filled shared tail block (the ONLY device copy a prefix
    hit ever pays). ``dst``/``src`` are traced, one executable serves
    every pair. Quantized pools copy payload AND scales in the same
    dispatch — a CoW'd block is exact, not re-quantized."""
    def _copy(kv):
        if isinstance(kv, dict):
            return {"q": kv["q"].at[:, dst].set(kv["q"][:, src]),
                    "scale": kv["scale"].at[:, dst].set(kv["scale"][:, src])}
        return kv.at[:, dst].set(kv[:, src])

    return {"k": _copy(pool["k"]), "v": _copy(pool["v"])}


def max_admit_rows(cfg: TransformerConfig) -> int | None:
    """The most rows one admission dispatch may hold (None: as many as
    there are). A sparse layer scores a whole virtual row per block of
    queries, so a batch of such rows is a batch of those score tensors:
    one row a dispatch. An admission's scratch cache is as many rows of
    ``cfg.cache_layers`` layers; a looped stack's is ``n_passes`` times a
    plain one's (2 GB a row of 1,280 tokens at Ouro-2.6B's sizes): one
    row a dispatch too. A latent model's admission expands every head's
    K/V and scores the whole prompt, [H, T0, T0] float32 a row (537 MB a
    row of 2,048 at Kanana-2-30B-A3B's 32 heads) beside weights and a
    store that fill 11.6 of 16 GB: one row a dispatch."""
    sparse = cfg.mixer_types and cfg.layers_of(MIXER_SPARSE)
    return 1 if sparse or cfg.n_passes > 1 or cfg.kv_lora_rank else None


# ---------------------------------------------------------------------------
# Tensor-parallel serving layout (serving/continuous.py's tp_shards knob)
# ---------------------------------------------------------------------------
#
# A tp-sharded decoder runs every executable above over a tensor mesh:
# weights carry the Megatron column/row split from
# models/transformer.py:partition_rules, and the KV storage — dense rows
# or the paged block pool, fp or quantized — is sharded over the KV-HEAD
# axis. Block ids index the (unsharded) block dim, so they stay
# host-global: the allocator, the prefix trie, refcount/CoW, and the
# export/import handoff never see the split. Per-head attention math is
# fully local to a shard; the only cross-shard reductions are the
# row-parallel output projections (wo, mlp down), which GSPMD inserts
# from the weight shardings.


def _kv_side_spec(side, axis: str, pp_axis: str | None = None):
    """Spec for one side (k or v) of a KV store whose head dim is the
    second-to-last payload dim — covers the dense [L, slots, T, Hkv, hd]
    cache, the paged [L, N, Bs, Hkv, hd] pool, and the quantized
    ``{"q", "scale"}`` pair (scales drop the trailing hd). ``pp_axis``
    additionally shards the leading LAYER dim — the pipeline-parallel
    serving layout, where each stage holds the KV for its own layer
    range. Block ids index dims the split never touches, so the
    allocator/trie/handoff host code is pp-blind exactly as it is
    tp-blind."""
    from jax.sharding import PartitionSpec as P

    def _spec(arr):
        return P(pp_axis, *([None] * (arr.ndim - 3)), axis, None)

    if isinstance(side, dict):
        return {"q": _spec(side["q"]),
                "scale": P(pp_axis, *([None] * (side["scale"].ndim - 2)),
                           axis)}
    return _spec(side)


def decode_state_specs(state, axis: str = "tensor",
                       pp_axis: str | None = None):
    """PartitionSpec pytree for a decode state on a tensor-parallel
    serving mesh: KV payload sharded over the KV-head axis (and, with
    ``pp_axis``, over the layer dim), every other leaf (tables, lengths,
    logits, RNG key) replicated."""
    from jax.sharding import PartitionSpec as P

    def _replicate(tree):
        return jax.tree.map(lambda _: P(), tree)

    specs = {}
    for name, sub in state.items():
        if name in ("pool", "cache"):
            specs[name] = {s: _kv_side_spec(sub[s], axis, pp_axis)
                           for s in sub}
        else:
            specs[name] = _replicate(sub)
    return specs


def shard_decode_state(state, mesh, axis: str = "tensor",
                       pp_axis: str | None = None):
    """Place a decode state (or a dense prefix pool — any {"k","v"}
    tree) onto ``mesh`` with the KV-head split of
    :func:`decode_state_specs`."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    if set(state) == {"k", "v"}:
        specs = {s: _kv_side_spec(state[s], axis, pp_axis) for s in state}
    else:
        specs = decode_state_specs(state, axis, pp_axis)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(state, shardings)
