"""A ``mixer_types`` model (lightning linear-attention layers and
block-sparse attention layers in one decoder) against the plain reference
``benchmarks/reference/minicpm_sala_f32.py`` on seeded weights, at the
``sala-test-tiny`` preset in float32: the cache-free forward, prefill and
decode through the continuous decoder, the forms of the lightning
equation, the selection's invariants, the decode kernel that reads the
selected blocks out of the pool (in interpret mode, at the widths it
engages at) against the gather it replaces, the muP scalings, and what
refuses such a model by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.sessions import install_weights
from benchmarks.reference import minicpm_sala_f32 as ref
from kubeflow_tpu.models import decode, transformer
from kubeflow_tpu.ops import linear_attention as la
from kubeflow_tpu.ops import sparse_attention as sa
from kubeflow_tpu.serving.continuous import ContinuousDecoder

SEED = 3
CFG = transformer.config("sala-test-tiny", dtype=jnp.float32)
SPEC = CFG.sparse_spec
W = ref.Widths(
    vocab_size=CFG.vocab_size, hidden_size=CFG.d_model,
    intermediate_size=CFG.d_ff, num_attention_heads=CFG.n_heads,
    num_key_value_heads=CFG.n_kv_heads, head_dim=CFG.head_dim,
    rope_theta=CFG.rope_theta, rms_norm_eps=CFG.norm_eps,
    mixer_types=CFG.mixer_types, scale_emb=12.0, scale_depth=1.4,
    mup_denominator=32, dim_model_base=16, kernel_size=SPEC.kernel,
    kernel_stride=SPEC.stride, block_size=SPEC.block, topk=SPEC.topk,
    init_blocks=SPEC.init_blocks, window_size=SPEC.window,
    dense_len=SPEC.dense_len)
TOL = 2e-5  # float32 against float32, summed in another order


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return install_weights(transformer.init(jax.random.PRNGKey(0), CFG),
                           SEED, W)


def _reference_logits(prompts, outs):
    """The reference's logits at the position each served token was
    chosen from: list of [len(out), V] arrays."""
    length = ref.padded_length(W, max(len(p) + len(o)
                                      for p, o in zip(prompts, outs)))
    n_out = max(len(o) for o in outs)
    tokens = np.zeros((len(prompts), length), np.int32)
    positions = np.zeros((len(prompts), n_out), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        tokens[i, :len(p) + len(o)] = list(p) + list(o)
        positions[i, :len(o)] = len(p) - 1 + np.arange(len(o))
    logits = np.asarray(ref.logits_at(SEED, W, tokens, positions))
    return [logits[i, :len(o)] for i, o in enumerate(outs)]


def _widest_gap(prompts, outs) -> float:
    return max(float((lg.max(-1) - lg[np.arange(len(o)), o]).max())
               for lg, o in zip(_reference_logits(prompts, outs), outs))


def _decoder(params, **kw):
    kw = {"slots": 4, "prefill_len": 32, "max_new_tokens": 40,
          "kv_layout": "paged", "kv_block_size": SPEC.block,
          "prefill_chunk_tokens": 16, "max_prompt_len": 88,
          "prefill_len_buckets": 1, **kw}
    return ContinuousDecoder(params, CFG, **kw)


# Rows on both sides of dense_len 32 (10, 5, 16 under; 40, 70, 80 over),
# rows that cross it while they decode (28, 16), and eight of them on four
# slots, so every slot is used again without a retire_row in between.
LENGTHS = (10, 28, 40, 70, 5, 33, 16, 80)


@pytest.fixture(scope="module")
def served(params):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist() for n in LENGTHS]
    dec = _decoder(params)
    try:
        handles = [dec.submit(p, 40) for p in prompts]
        outs = [h.result(timeout=600)["tokens"] for h in handles]
        metrics = dec.metrics()
    finally:
        dec.stop()
    return prompts, outs, metrics


def test_forward_without_a_cache_matches_the_reference(params):
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 128),
                                           0, CFG.vocab_size))
    positions = np.tile(np.arange(128)[None], (2, 1))
    want = ref.logits_at(SEED, W, tokens, positions)
    got = transformer.apply(params, jnp.asarray(tokens), CFG)
    assert float(jnp.abs(want - got).max()) < TOL


@pytest.mark.parametrize("row", range(len(LENGTHS)))
def test_every_decoded_position_is_the_references(served, row):
    """Prefill (chunked where the prompt is over 16 tokens) then decode:
    each served token is the reference's first at its position."""
    prompts, outs, _ = served
    assert len(outs[row]) == 40
    assert _widest_gap([prompts[row]], [outs[row]]) < TOL


def test_the_decoder_counts_state_and_selection(served, params):
    _, _, m = served
    held = jax.eval_shape(lambda: decode.init_paged_state(
        CFG, 4, 4 * 16, SPEC.block, 16))
    assert m["state_bytes"] == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree.leaves((held["lin_state"], held["ckeys"])))
    assert m["rows_dense"] > 0 and m["rows_sparse"] > 0
    # Per-layer mixer trees: no stacked q/k/v to hold transposed.
    assert m["qkv_held_transposed"] == 0
    assert m["rows_dense"] + m["rows_sparse"] == m["tokens_emitted"]
    assert 0 < m["sparse_tokens_attended"] < m["sparse_tokens_in_context"]
    assert m["prefill_chunks"] > 0
    # Only the sparse layers hold K/V.
    assert m["kv_bytes_per_token"] == 2 * 2 * CFG.n_kv_heads * CFG.head_dim * 4


def test_chunked_admission_equals_monolithic(params, served):
    prompts, outs, _ = served
    pick = [2, 3, 7]  # 40, 70 and 80 tokens: 3 to 5 chunks of 16
    dec = _decoder(params, prefill_len=88, prefill_chunk_tokens=0,
                   max_prompt_len=0, prefill_len_buckets=0)
    try:
        whole = [dec.submit(prompts[i], 40).result(timeout=600)["tokens"]
                 for i in pick]
    finally:
        dec.stop()
    assert whole == [outs[i] for i in pick]


def test_fused_decode_steps_equal_one_step_a_dispatch(params, served):
    """``chunk_size`` steps in one dispatch (what the long-decode cell
    runs) carry state, compressed keys and the pool through the scan: the
    same tokens, a row crossing dense_len inside a chunk among them."""
    prompts, outs, _ = served
    pick = [1, 3, 6]  # 28 and 16 cross dense_len 32 while they decode
    dec = _decoder(params, chunk_size=4)
    try:
        handles = [dec.submit(prompts[i], 40) for i in pick]
        fused = [h.result(timeout=600)["tokens"] for h in handles]
        m = dec.metrics()
    finally:
        dec.stop()
    assert fused == [outs[i] for i in pick]
    assert m["decode_steps"] >= 4 * m["decode_dispatches"] > 0


def test_retire_row_zeroes_the_recurrent_state():
    state = decode.init_paged_state(CFG, 2, 16, SPEC.block, 8)
    state["lin_state"] = tuple(s + 1.0 for s in state["lin_state"])
    state = decode.retire_row(state, 1)
    for s in state["lin_state"]:
        assert float(jnp.abs(s[1]).max()) == 0.0
        assert float(s[0].min()) == 1.0
    assert int(state["length"][1]) == 8 * SPEC.block


def _qkv(b=2, s=37, h=4, hd=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(x, (b, s, h, hd), jnp.float32)
               for x in keys[:3])
    state = jax.random.normal(keys[3], (b, h, hd, hd), jnp.float32)
    return q, k, v, state


def _lightning_recurrent(q, k, v, state, slopes):
    """The token recurrence over a span, the definition itself: q, k, v
    [B, S, H, hd] → (o [B, S, H, hd], state after the span)."""
    out = []
    for t in range(q.shape[1]):
        o, state = la.lightning_step(q[:, t], k[:, t], v[:, t], state, slopes)
        out.append(o)
    return jnp.stack(out, axis=1), state


def _lightning_quadratic(q, k, v, state, slopes):
    """o_t = sum_{j<=t} lam^(t-j) (q_t.k_j / sqrt(hd)) v_j
    + lam^(t+1) q_t S_0 / sqrt(hd), as one masked product."""
    s, hd = q.shape[1], q.shape[-1]
    t = np.arange(s)
    decay = np.where(t[:, None] >= t[None, :],
                     np.exp(-np.asarray(slopes)[:, None, None]
                            * np.abs(t[:, None] - t[None, :])), 0.0)
    scores = np.einsum("bihd,bjhd->bhij", q, k) * decay[None] * hd ** -0.5
    o = np.einsum("bhij,bjhd->bihd", scores, v)
    carried = np.einsum("bihd,bhde->bihe", q, state) * hd ** -0.5
    return o + carried * np.exp(
        -np.asarray(slopes)[None, None, :, None] * (t + 1)[None, :, None, None])


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_three_forms_of_the_lightning_equation_agree(chunk):
    q, k, v, state = _qkv()
    slopes = la.lightning_slopes(4)
    rec, rec_state = _lightning_recurrent(q, k, v, state, slopes)
    chunked, chunked_state = la.lightning_chunked(q, k, v, state, slopes,
                                                  chunk=chunk)
    quad = _lightning_quadratic(*(np.asarray(a, np.float64)
                                  for a in (q, k, v, state)), slopes)
    assert float(jnp.abs(rec - chunked).max()) < 1e-4
    assert float(np.abs(np.asarray(rec) - quad).max()) < 1e-4
    assert float(jnp.abs(rec_state - chunked_state).max()) < 1e-4


def test_right_padding_neither_enters_nor_decays_the_state():
    q, k, v, state = _qkv(s=24)
    slopes = la.lightning_slopes(4)
    n_valid = jnp.array([24, 13])
    out, after = la.lightning_chunked(q, k, v, state, slopes, n_valid,
                                      chunk=8)
    short, short_after = _lightning_recurrent(
        q[1:, :13], k[1:, :13], v[1:, :13], state[1:], slopes)
    assert float(jnp.abs(out[1, :13] - short[0]).max()) < 1e-4
    assert float(jnp.abs(after[1] - short_after[0]).max()) < 1e-4


def _selection(pos, spec=SPEC, n_blocks=12, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(keys[0], (1, 2, 2, len(pos), 16), jnp.float32)
    k_row = jax.random.normal(keys[1], (1, 2, n_blocks * spec.block, 16),
                              jnp.float32)
    idx, ok = sa.select_blocks(q, sa.compress_keys(k_row, spec),
                               jnp.asarray(pos)[None], n_blocks, spec)
    return np.asarray(idx[0]), np.asarray(ok[0]), q, k_row


@pytest.mark.parametrize("pos", [40, 63, 64, 95])
def test_selection_holds_block_zero_and_the_local_blocks(pos):
    idx, ok, _, _ = _selection([pos])
    for head in range(2):
        chosen = set(idx[head, 0][ok[head, 0]].tolist())
        local = set(range((pos - SPEC.window + 1) // SPEC.block,
                          pos // SPEC.block + 1))
        assert {0} | local <= chosen
        assert len(chosen) == int(ok[head, 0].sum()) <= SPEC.topk
        assert max(chosen) <= pos // SPEC.block


def test_a_context_up_to_dense_len_reads_every_block():
    idx, ok, _, _ = _selection([SPEC.dense_len - 1])
    assert set(idx[0, 0][ok[0, 0]].tolist()) == set(
        range(SPEC.dense_len // SPEC.block))


def test_topk_of_every_block_is_dense_attention():
    spec = dataclasses.replace(SPEC, topk=12, dense_len=0)
    pos = np.arange(40, 96)
    _, _, q, k_row = _selection(pos, spec)
    v_row = jnp.flip(k_row, axis=-1)
    q_bshd = q[0].transpose(2, 0, 1, 3).reshape(1, len(pos), 4, 16)
    got = sa.attend_span(q_bshd, jnp.asarray(pos)[None], k_row, v_row,
                         sa.compress_keys(k_row, spec), spec, q_block=16)
    scores = jnp.einsum("bkgqd,bktd->bkgqt", q, k_row) * 16 ** -0.5
    causal = jnp.arange(k_row.shape[2])[None, :] <= jnp.asarray(pos)[:, None]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bkgqt,bktd->bkgqd", p, v_row)
    want = want[0].transpose(2, 0, 1, 3).reshape(1, len(pos), 4, 16)
    assert float(jnp.abs(got - want).max()) < 1e-5


# What select_blocks must pick: the n largest of the float32 ranks, the
# largest first and of equals the lower block first, which is what
# ``lax.top_k`` of one row gives and what a stable sort of the negated
# ranks gives numpy.
WIDE = sa.SparseSpec(kernel=32, stride=16, block=64, topk=16, init_blocks=1,
                     window=512, dense_len=2048)
ORACLE_CASES = {
    # name: (spec, blocks a row, positions of the queries, keys)
    "tied-scores-across-blocks": (SPEC, 12, [95, 80], "periodic"),
    "at-dense-len": (SPEC, 12, [SPEC.dense_len - 1], "random"),
    "past-dense-len": (SPEC, 12, [SPEC.dense_len], "random"),
    "pos-in-block-0": (SPEC, 12, [3], "random"),
    "row-shorter-than-n": (SPEC, 12, [20], "random"),
    "queries-dense-and-sparse": (SPEC, 12, [5, 31, 32, 60, 95], "random"),
    "wide-spec-96-blocks": (WIDE, 96, [700, 2047, 2048, 6143], "random"),
    "wide-spec-tied": (WIDE, 96, [6143, 4000], "periodic"),
}


@pytest.mark.parametrize("case", sorted(ORACLE_CASES))
def test_the_selection_is_a_stable_sorts(case):
    spec, n_blocks, pos, keys_are = ORACLE_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    q = jax.random.normal(keys[0], (2, 2, 2, len(pos), 16), jnp.float32)
    k_row = jax.random.normal(keys[1], (2, 2, n_blocks * spec.block, 16),
                              jnp.float32)
    if keys_are == "periodic":  # every block holds the same keys
        k_row = jnp.tile(k_row[:, :, :spec.block], (1, 1, n_blocks, 1))
    pos = np.tile(np.asarray(pos, np.int32), (2, 1))
    args = (q, sa.compress_keys(k_row, spec), jnp.asarray(pos), n_blocks,
            spec)
    idx, ok = (np.asarray(a) for a in sa.select_blocks(*args))
    ranks = np.asarray(sa._block_ranks(*args)[0])
    n = spec.n_select(n_blocks * spec.block)
    want_idx = np.argsort(-ranks, axis=-1, kind="stable")[..., :n]
    dense = (pos + 1 <= spec.dense_len)[:, None, :, None]
    want_ok = (np.take_along_axis(ranks, want_idx, -1) > -1e29) & (
        dense | (np.arange(n) < spec.topk))
    assert idx.shape == want_idx.shape == (2, 2, pos.shape[1], n)
    assert idx.dtype == np.int32 and ok.dtype == np.bool_
    tied = False
    for b, h, j in np.ndindex(*idx.shape[:3]):
        mine, theirs = idx[b, h, j], want_idx[b, h, j]
        count = int(ok[b, h, j].sum())
        assert (ok[b, h, j] == (np.arange(n) < count)).all()  # a prefix
        assert (ok[b, h, j] == want_ok[b, h, j]).all()
        assert set(mine[:count]) == set(theirs[:count])
        assert len(set(mine[:count])) == count
        home = pos[b, j] // spec.block
        assert mine[:count].max() <= home  # nothing past the query
        # What the decode read derives: the count, and the slot to cut.
        assert home in mine[:count]
        assert np.argmax(mine == home) == np.argmax(theirs == home) < count
        # The forced ones first, then by score: the order too.
        assert (mine == theirs).all()
        rank = ranks[b, h, j]
        left_out = np.setdiff1d(np.arange(n_blocks), mine[:count])
        if count and len(left_out) and (
                rank[left_out].max() == rank[mine[count - 1]]):
            tied = True  # the last block taken ties with one left out
            assert mine[count - 1] < left_out[
                rank[left_out] == rank[mine[count - 1]]].min()
    assert tied or keys_are != "periodic"


# The decode read at the widths the kernel engages at (head_dim 128,
# block 64), one batch: a row per position around the block and dense_len
# boundaries, a long row, and a row with nothing allocated. The pool is
# handed out in a shuffled order, so no row's blocks are contiguous or
# ascending.
KSPEC = sa.SparseSpec(kernel=32, stride=16, block=64, topk=16, init_blocks=1,
                      window=512, dense_len=2048)
KPOS = (0, 63, 64, 4095, KSPEC.dense_len - 1, KSPEC.dense_len,
        KSPEC.dense_len + 1, 6000, 200)
UNALLOCATED = len(KPOS) - 1  # the last row's table is all sentinels


@pytest.fixture(scope="module")
def pool_read():
    b, hkv, group, hd, mb, n_pool, layer = len(KPOS), 2, 4, 128, 96, 400, 1
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    pool_k, pool_v = (jax.random.normal(k, (2, n_pool, hkv, KSPEC.block, hd),
                                        jnp.float32) for k in keys[:2])
    q = jax.random.normal(keys[2], (b, hkv, group, hd), jnp.float32)
    order = np.random.default_rng(5).permutation(n_pool).tolist()
    table = np.full((b, mb), n_pool, np.int32)
    for row, pos in enumerate(KPOS[:UNALLOCATED]):
        for j in range(pos // KSPEC.block + 1):
            table[row, j] = order.pop()
    assert (np.diff(table[3, :64]) < 0).any()  # scattered, not monotone
    table, pos = jnp.asarray(table), jnp.asarray(KPOS, jnp.int32)
    k_rows = decode._hm_row(pool_k, layer, jnp.minimum(table, n_pool - 1))
    idx, ok = sa.select_blocks(q[:, :, :, None],
                               sa.compress_keys(k_rows, KSPEC), pos[:, None],
                               mb, KSPEC)
    idx, ok = idx[:, :, 0], ok[:, :, 0]
    read = {impl: np.asarray(sa.sparse_decode_attention(
        q, pool_k, pool_v, layer, table, idx, ok, pos, KSPEC,
        implementation=impl, interpret=True)) for impl in ("xla", "pallas")}
    return np.asarray(ok), read


@pytest.mark.parametrize("row", range(len(KPOS)))
def test_the_block_table_kernel_reads_what_the_gather_reads(pool_read, row):
    _, read = pool_read
    assert np.abs(read["xla"][row]).max() > 0.01
    assert np.abs(read["pallas"][row] - read["xla"][row]).max() < 1e-5


@pytest.mark.parametrize("row", range(len(KPOS)))
def test_the_selection_counts_are_a_prefix(pool_read, row):
    """What the kernel is told, a count, says all ``ok`` says: the real
    slots come first, ``topk`` of them past dense_len and every visible
    block up to it."""
    ok, _ = pool_read
    pos, n = KPOS[row], ok.shape[-1]
    assert n == KSPEC.n_select(96 * KSPEC.block) == 32
    want = (pos // KSPEC.block + 1 if pos + 1 <= KSPEC.dense_len
            else KSPEC.topk)
    for head in range(2):
        count = int(ok[row, head].sum())
        assert count == want
        assert (ok[row, head] == (np.arange(n) < count)).all()


def test_the_kernel_reads_a_bfloat16_pool_in_chunks_of_any_size():
    """The cell's dtype; chunks of 5 blocks do not divide the 8 slots."""
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    pool_k, pool_v = (jax.random.normal(k, (1, 24, 2, 64, 128),
                                        jnp.bfloat16) for k in keys[:2])
    q = jax.random.normal(keys[2], (3, 2, 16, 128), jnp.bfloat16)
    rng = np.random.default_rng(6)
    table = jnp.asarray(rng.integers(0, 24, (3, 12)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, 12, (3, 2, 8)), jnp.int32)
    count = jnp.asarray([[8, 3], [1, 6], [0, 5]], jnp.int32)
    cut, keep = count - 1, jnp.asarray([0, 17, 63], jnp.int32)
    got = sa._attend_pool_pallas(q, pool_k, pool_v, 0, table, idx, count, cut,
                                 keep, blocks_per_chunk=5, interpret=True)
    phys = jnp.take_along_axis(jnp.broadcast_to(table[:, None], (3, 2, 12)),
                               idx, axis=2)
    heads = jnp.arange(2)[None, :, None]
    # attend_selected masks by virtual position: say the cut slot's block
    # is the row's last, every other slot's its first.
    virtual = jnp.where(jnp.arange(8) == cut[..., None], 7, 0)
    want = sa.attend_selected(
        q, pool_k[0, phys, heads], pool_v[0, phys, heads], virtual,
        jnp.arange(8) < count[..., None], 7 * 64 + keep, KSPEC)
    diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    assert float(diff.max()) < 0.02
    assert float(jnp.abs(got[2, 0].astype(jnp.float32)).max()) == 0.0


def test_an_explicit_kernel_off_the_tpu_or_off_its_shapes_raises():
    pool = jnp.zeros((1, 4, 2, SPEC.block, 16))
    args = (jnp.zeros((1, 2, 2, 16)), pool, pool, 0,
            jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 2, 2), jnp.int32),
            jnp.ones((1, 2, 2), bool), jnp.zeros((1,), jnp.int32), SPEC)
    with pytest.raises(ValueError, match="implementation='pallas'"):
        sa.sparse_decode_attention(*args, implementation="pallas")
    with pytest.raises(ValueError, match="unknown implementation"):
        sa.sparse_decode_attention(*args, implementation="triton")
    assert sa.decode_implementation(SPEC, 16) == "xla"
    assert sa.sparse_decode_attention(*args).shape == (1, 2, 2, 16)


def test_the_decoder_says_which_read_it_compiled(served):
    _, _, m = served
    assert m["sparse_attn_impl"] == "xla"  # the CPU, and head_dim 16


@pytest.mark.parametrize("scaling", ["embed_scale", "residual_scale",
                                     "head_scale"])
def test_leaving_out_a_mup_scaling_fails(params, scaling):
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 128),
                                           0, CFG.vocab_size))
    positions = np.arange(128)[None]
    want = ref.logits_at(SEED, W, tokens, positions)
    got = transformer.apply(params, jnp.asarray(tokens),
                            dataclasses.replace(CFG, **{scaling: 1.0}))
    assert float(jnp.abs(want - got).max()) > 100 * TOL


REFUSED = {
    "the dense KV layout": {"kv_layout": "dense", "prefill_chunk_tokens": 0,
                            "max_prompt_len": 0},
    "prefix cache": {"prefix_cache_slots": 2},
    "speculative decoding": {"speculative_k": 2},
    "int8 KV": {"kv_dtype": "int8"},
    "kv_fused": {"kv_fused": True},
    "tensor parallelism": {"tp_shards": 2},
    "context parallelism": {"cp_shards": 2},
    "pipeline parallelism": {"pp_stages": 2},
    "_suspend_stream": {"host_kv_bytes": 1 << 20},
    "export_blocks / import_blocks": {"role": "prefill"},
    "kv_directory": {"kv_directory": object()},
}


@pytest.mark.parametrize("mechanism", sorted(REFUSED))
def test_what_moves_kv_alone_refuses_the_model_by_name(mechanism):
    shapes = jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), CFG))
    with pytest.raises(ValueError) as refused:
        _decoder(shapes, **REFUSED[mechanism])
    assert "mixer_types" in str(refused.value)
    assert mechanism in str(refused.value)


def test_a_kv_block_that_is_not_the_selections_block_is_refused(params):
    with pytest.raises(ValueError, match="kv_block_size"):
        _decoder(params, kv_block_size=4)


def test_config_validates_its_mixers_once():
    with pytest.raises(ValueError, match="mixer_types names 2 layers"):
        dataclasses.replace(CFG, mixer_types=("minicpm4", "lightning-attn"))
    with pytest.raises(ValueError, match="unknown mixer kind"):
        dataclasses.replace(CFG, mixer_types=("mamba",) * CFG.n_layers)
    with pytest.raises(ValueError, match="more than topk"):
        dataclasses.replace(CFG, sparse_topk=3)
    from kubeflow_tpu.models.registry import get_model, list_models

    assert {"sala-test-tiny", "minicpm-sala-9b"} <= set(list_models())
    big = get_model("minicpm-sala-9b").config
    assert big.layers_of("minicpm4") == (0, 9, 16, 17, 22, 29, 30, 31)
    assert big.head_dim == 128 and len(big.mixer_types) == 32


def test_init_draws_matrices_at_the_compute_dtype():
    cfg = transformer.config("sala-test-tiny")
    tree = transformer.init(jax.random.PRNGKey(0), cfg)
    held = transformer.serving_params(tree, cfg)
    # Nothing left to cast: every leaf comes back as the same object.
    assert all(a is b for a, b in zip(jax.tree.leaves(tree),
                                      jax.tree.leaves(held)))
    assert tree["layers"][0]["mixer"]["wq"].dtype == jnp.bfloat16
    assert tree["layers"][1]["mixer"]["o_norm"].dtype == jnp.float32
