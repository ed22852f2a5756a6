"""Latent attention (one latent row a token, read by every head with the
up-projections absorbed) and the dropless sigmoid-routed expert layer,
against the plain reference ``benchmarks/reference/kanana_f32.py`` on
seeded weights, at the ``kanana-test-tiny`` preset in float32: the
cache-free forward, prefill then decode through the cache with rows of
unequal length and slots reused, the absorbed read against the expanded
one on the same cache, the router's contract (nothing dropped, the bias
selects and never weighs), the rotary layout the program holds, the
counts, what refuses such a model by name, and that each planted fault
moves the logits by more than the tolerance these comparisons use."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.latent_moe import install_weights
from benchmarks.harness import registry
from benchmarks.reference import kanana_f32 as ref
from kubeflow_tpu.models import decode, transformer
from kubeflow_tpu.ops import attention
from kubeflow_tpu.ops.attention import dense_decode_attention
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.kv_allocator import kv_bytes_per_token

SEED = 5
CFG = transformer.config("kanana-test-tiny", dtype=jnp.float32)
W = ref.Widths.from_config(registry.load_json("configs",
                                              "rehearsal-tiny-kanana"))
# float32 against float32 at "highest" precision, summed in another order
# (the absorbed read multiplies the up-projections on the other side of
# the products) through 3 layers: logits of standard deviation 1 agree to
# 1e-5 here; 1e-4 leaves a digit. The least of the planted faults moves
# them by more than 0.1 (the last test), bfloat16 in the reference's place
# by about 0.01.
TOL = 1e-4


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return install_weights(transformer.init(jax.random.PRNGKey(0), CFG),
                           SEED, W)


def test_the_preset_is_the_rehearsal_configuration():
    assert (W.hidden_size, W.num_hidden_layers, W.n_routed_experts,
            W.num_experts_per_tok, W.kv_lora_rank, W.qk_rope_head_dim) == (
        CFG.d_model, CFG.n_layers, CFG.n_experts, CFG.expert_top_k,
        CFG.kv_lora_rank, CFG.qk_rope_head_dim)
    assert CFG.latent_width == 128 and CFG.rope_dim == 16
    assert CFG.cache_layers == 3


def test_the_cut_holds_the_parameters_the_sizing_counts():
    cfg = transformer.config("kanana-2-30b-a3b", n_layers=6)
    shapes = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0),
                                                     cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 3_789_584_000
    assert shapes["layers"]["mlp"]["gate"].shape == (5, 128, 2048, 768)
    assert shapes["dense_layers"]["mlp"]["gate"].shape == (1, 2048, 6144)
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 64, 8192))
    assert cache["k"].shape == (6, 64, 8192, 640) and cache["v"] is None
    assert kv_bytes_per_token(cfg.cache_layers, cfg.n_kv_heads, cfg.head_dim,
                              2, latent=576) == 6_912


def _reference_logits(prompts, outs):
    """The reference's logits at the position each served token was
    chosen from: list of [len(out), V] arrays."""
    length = max(len(p) + len(o) for p, o in zip(prompts, outs))
    n_out = max(len(o) for o in outs)
    tokens = np.zeros((len(prompts), length), np.int32)
    positions = np.zeros((len(prompts), n_out), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        tokens[i, :len(p) + len(o)] = list(p) + list(o)
        positions[i, :len(o)] = len(p) - 1 + np.arange(len(o))
    logits = np.asarray(ref.logits_at(SEED, W, tokens, positions))
    return [logits[i, :len(o)] for i, o in enumerate(outs)]


def test_forward_without_a_cache_matches_the_reference(params):
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 40),
                                           0, CFG.vocab_size))
    positions = np.tile(np.arange(40)[None], (2, 1))
    want = ref.logits_at(SEED, W, tokens, positions)
    got = transformer.apply(params, jnp.asarray(tokens), CFG)
    assert float(jnp.abs(want - got).max()) < TOL


def test_prefill_then_decode_logits_are_the_references(params):
    """Rows of unequal length admitted in one batch, then decoded through
    the latent cache a token at a time: the logits the state holds after
    every step, against the reference's full forward."""
    lens = np.array([16, 7, 11])
    rng = np.random.default_rng(11)
    seqs = rng.integers(0, CFG.vocab_size, (3, 16))
    state = decode.init_decode_state(CFG, 3, 24)
    prompt = np.where(np.arange(16)[None] < lens[:, None], seqs, 0)
    state, last = decode._admit_rows_body(
        state, params, CFG, jnp.arange(3), jnp.asarray(prompt),
        jnp.asarray(lens), jnp.full((3,), 6), jnp.zeros((3,)))
    got, outs = [np.asarray(last)], []
    for _ in range(5):
        state, tok, _ = decode.decode_step(state, params, CFG)
        outs.append(np.asarray(tok))
        got.append(np.asarray(state["last_logits"]))
    got, outs = np.stack(got, 1), np.stack(outs, 1)      # [3, 6, V], [3, 5]
    want = _reference_logits([prompt[i, :n].tolist() for i, n in
                              enumerate(lens)],
                             [outs[i].tolist() + [0] for i in range(3)])
    assert max(float(np.abs(w - g).max()) for w, g in zip(want, got)) < TOL
    assert state["moe_touched"].shape == (2,)
    # Five steps of three rows, 2 choices each, in each expert layer.
    assert np.asarray(state["moe_routed"]).sum(-1).tolist() == [30, 30]


# Eight rows of unequal length on four slots: every slot is used a second
# time, by a request that is admitted after the first has retired.
LENGTHS = (10, 28, 5, 17, 32, 9, 21, 14)
PROMPTS = [np.random.default_rng(i).integers(0, CFG.vocab_size, n).tolist()
           for i, n in enumerate(LENGTHS)]


def _decoder(params, cfg=CFG, **kw):
    kw = {"slots": 4, "prefill_len": 32, "max_new_tokens": 24, **kw}
    return ContinuousDecoder(params, cfg, **kw)


@pytest.fixture(scope="module")
def served(params):
    dec = _decoder(params)
    try:
        handles = [dec.submit(p, 24) for p in PROMPTS]
        outs = [h.result(timeout=600)["tokens"] for h in handles]
        return outs, dec.metrics()
    finally:
        dec.stop()


def test_every_served_position_is_the_references(served):
    """Through the scheduler: rows 4-7 run on slots that rows 0-3 have
    left, so every layer of their latent rows starts clean."""
    outs, _ = served
    want = _reference_logits(PROMPTS, outs)
    for lg, o in zip(want, outs):
        assert len(o) == 24
        assert float((lg.max(-1) - lg[np.arange(24), o]).max()) < TOL


def test_the_decoder_counts_what_a_step_reads(served):
    _, m = served
    assert m["cache_layers"] == 3 and m["kv_bytes_per_token"] == 3 * 48 * 4
    assert m["dense_attn_impl"] == "xla"  # off the TPU
    assert m["kv_tokens_attended"] == sum(
        n + j for n in LENGTHS for j in range(1, 25))
    routed, touched = np.asarray(m["moe_routed"]), np.asarray(m["moe_touched"])
    assert routed.shape == (2, 8) and touched.shape == (2,)
    # Every emitted token of a decode step took two experts in each expert
    # layer; the admissions' prefills are not counted.
    assert routed.sum() == 2 * 2 * m["tokens_emitted"] == 2 * 2 * 8 * 24
    assert (touched <= 8 * m["decode_steps"]).all() and (touched > 0).all()


def test_a_slot_reused_after_a_retire_starts_clean(params):
    """A row admitted into a slot whose last user wrote 30 positions reads
    nothing of them: its logits are a fresh state's."""
    state = decode.init_decode_state(CFG, 1, 40)
    one = jnp.ones((1,), jnp.int32)
    long = jnp.asarray([PROMPTS[4]])
    state, _ = decode._admit_rows_body(state, params, CFG, 0 * one, long,
                                       32 * one, 4 * one, jnp.zeros((1,)))
    for _ in range(3):
        state, _, _ = decode.decode_step(state, params, CFG)
    state = decode.retire_row(state, 0)
    short = jnp.asarray([PROMPTS[2] + [0] * 27])
    reused, a = decode._admit_rows_body(state, params, CFG, 0 * one, short,
                                        5 * one, 4 * one, jnp.zeros((1,)))
    fresh, b = decode._admit_rows_body(decode.init_decode_state(CFG, 1, 40),
                                       params, CFG, 0 * one, short, 5 * one,
                                       4 * one, jnp.zeros((1,)))
    assert bool((a == b).all())
    assert bool((reused["cache"]["k"] == fresh["cache"]["k"]).all())
    for _ in range(3):
        reused, ta, _ = decode.decode_step(reused, params, CFG)
        fresh, tb, _ = decode.decode_step(fresh, params, CFG)
        assert bool((reused["last_logits"] == fresh["last_logits"]).all())


def test_the_absorbed_read_is_the_expanded_one_on_the_same_cache(params):
    """One layer's attention for a token a row at unequal positions over a
    store that holds other rows' junk past each row's length: the absorbed
    form (the decode step's) against every head's K/V expanded from the
    same rows."""
    attn = jax.tree.map(lambda w: w[0], params["layers"]["attn"])
    b, t = 3, 20
    key = jax.random.PRNGKey(3)
    store = jax.random.normal(key, (2, b, t, CFG.latent_width), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, 1, CFG.d_model))
    pos = jnp.asarray([4, 19, 0])
    cos, sin = jax.tree.map(lambda a: a[pos[:, None]],
                            transformer.rotary_frequencies(
                                CFG.rope_dim, t, theta=CFG.rope_theta))
    live = jnp.asarray([True, True, True])
    out, written, _ = decode._latent_attention(
        x, attn, CFG, (cos, sin), store, 1, pos, live,
        decode._dense_write_index(pos[:, None]))
    q_nope, q_rope, row = transformer.mla_inputs(x, attn, CFG, (cos, sin))
    rows = written[1]
    assert bool((rows[jnp.arange(b), pos] == row[:, 0]).all())
    mask = (jnp.arange(t)[None, None, :] <= pos[:, None, None])
    want = transformer.mla_expanded(q_nope, q_rope, rows, mask, attn, CFG)
    assert float(jnp.abs(out - want).max()) < 1e-5
    assert bool((written[0] == store[0]).all())  # the other layer untouched


def test_the_latent_kernel_reads_as_the_xla_path_does():
    """The Mosaic kernel in the interpreter against the XLA read, rows of
    length 0, shorter than a copy, and longer, bf16 store."""
    key = jax.random.PRNGKey(0)
    store = jax.random.normal(key, (2, 3, 300, 128)).astype(jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (3, 4, 128)).astype(jnp.bfloat16)
    lengths = jnp.asarray([0, 37, 300])
    kw = {"n_kv_heads": 1, "v_dim": 64, "sm_scale": 0.125}
    a = dense_decode_attention(q, store, None, 1, lengths,
                               implementation="pallas", interpret=True, **kw)
    b = dense_decode_attention(q, store, None, 1, lengths,
                               implementation="xla", **kw)
    assert a.shape == b.shape == (3, 4, 64)
    assert float(jnp.abs(a[0].astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(a.astype(jnp.float32)
                         - b.astype(jnp.float32)).max()) < 0.02


# The latent kernel at Kanana-2-30B-A3B's row: 512 latent columns, 64
# rotary, 64 zeros (the width a TPU lays a 576 row out at), 32 heads, the
# model's scale. A store of two chunks and 276 positions, so that its last
# chunk starts early; each case's row sits beside a free slot and a live
# row of another length (three rows, a row of length 0 between live ones).
CHUNK = attention._LATENT_CHUNK
LATENT_T = 2 * CHUNK + 276
LATENT_LENGTHS = {"empty": 0, "one": 1, "chunk-1": CHUNK - 1, "chunk": CHUNK,
                  "chunk+1": CHUNK + 1, "inside-the-last-chunk": 2 * CHUNK + 76,
                  "the-whole-store": LATENT_T}


@pytest.mark.parametrize("case", list(LATENT_LENGTHS))
def test_the_latent_kernel_at_kanana_widths(case):
    key = jax.random.PRNGKey(7)
    col = (jnp.arange(640) < 576).astype(jnp.bfloat16)
    lengths = jnp.asarray([LATENT_LENGTHS[case], 0, 2 * CHUNK - 3])
    store = (jax.random.normal(key, (2, 3, LATENT_T, 640)).astype(
        jnp.bfloat16) * col)
    q = (jax.random.normal(jax.random.fold_in(key, 1), (3, 32, 640)).astype(
        jnp.bfloat16) * col)
    scale = 192 ** -0.5
    got = dense_decode_attention(q, store, None, 1, lengths, n_kv_heads=1,
                                 v_dim=512, sm_scale=scale,
                                 implementation="pallas", interpret=True)
    want = attention._dense_decode_xla(q[:, None], store, None, 1, lengths,
                                       scale, 512)[:, 0]
    assert got.shape == (3, 32, 512)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    live = np.asarray(lengths) > 0
    assert not got[~live].any()
    assert np.abs(got[live]).max() > 0.05


def _layer_mlp(params):
    return jax.tree.map(lambda w: w[0], params["layers"]["mlp"])


def _by_hand(u, mlp, chosen, cfg=CFG):
    """sum over the chosen experts of w_e E_e(u), w from the sigmoid scores
    alone, every expert computed for every token, plus the shared MLP."""
    scores = jax.nn.sigmoid(u @ mlp["router"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    weight = cfg.routed_scaling * picked / picked.sum(-1, keepdims=True)
    dense = jnp.stack([
        (jax.nn.silu(u @ mlp["gate"][e]) * (u @ mlp["up"][e]))
        @ mlp["down"][e] for e in range(cfg.n_experts)], 1)  # [n, E, D]
    mix = jnp.zeros((u.shape[0], cfg.n_experts)).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(weight)
    shared = (jax.nn.silu(u @ mlp["shared_gate"]) * (u @ mlp["shared_up"])
              ) @ mlp["shared_down"]
    return jnp.einsum("ne,ned->nd", mix, dense) + shared


def test_no_token_is_dropped_when_every_row_picks_the_same_expert(params):
    """A bias that sends every token to expert 3: 64 tokens take it where
    the GShard layer's capacity would keep max(int(64 x 2 / 8 x 1.25), 2)
    = 20; each token's output is its two experts' and the shared one's."""
    mlp = _layer_mlp(params)
    mlp["bias"] = mlp["bias"].at[3].set(100.0)
    u = jax.random.normal(jax.random.PRNGKey(8), (64, CFG.d_model))
    y, routed = transformer.moe_dropless(u, mlp, CFG)
    assert int(routed[3]) == 64 and int(routed.sum()) == 128
    chosen = jax.lax.top_k(jax.nn.sigmoid(u @ mlp["router"]) + mlp["bias"],
                           2)[1]
    assert bool((chosen[:, 0] == 3).all())
    assert float(jnp.abs(y - _by_hand(u, mlp, chosen)).max()) < 1e-4


def test_the_bias_selects_and_never_weighs(params):
    """A large bias on two experts moves which experts are chosen, and the
    weights stay the sigmoid scores of the chosen ones normed and scaled:
    the bias enters no weight."""
    mlp = _layer_mlp(params)
    u = jax.random.normal(jax.random.PRNGKey(9), (32, CFG.d_model))
    scores = jax.nn.sigmoid(u @ mlp["router"])
    plain = jax.lax.top_k(scores + mlp["bias"], 2)[1]
    mlp["bias"] = mlp["bias"].at[jnp.asarray([1, 6])].add(5.0)
    biased = jax.lax.top_k(scores + mlp["bias"], 2)[1]
    assert bool((jnp.sort(biased, -1) == jnp.asarray([1, 6])).all())
    assert not bool((jnp.sort(plain, -1) == jnp.asarray([1, 6])).all())
    y, routed = transformer.moe_dropless(u, mlp, CFG)
    assert routed.tolist() == [0, 32, 0, 0, 0, 0, 32, 0]
    assert float(jnp.abs(y - _by_hand(u, mlp, biased)).max()) < 1e-4
    # Weighed by score + bias instead, the output would be another one.
    picked = jnp.take_along_axis(scores + mlp["bias"], biased, -1)
    weight = CFG.routed_scaling * picked / picked.sum(-1, keepdims=True)
    by_bias = jnp.einsum(
        "nk,nkd->nd", weight, jnp.stack([
            (jax.nn.silu(u @ mlp["gate"][e]) * (u @ mlp["up"][e]))
            @ mlp["down"][e] for e in (1, 6)], 1))
    shared = (jax.nn.silu(u @ mlp["shared_gate"]) * (u @ mlp["shared_up"])
              ) @ mlp["shared_down"]
    assert float(jnp.abs(y - (by_bias + shared)).max()) > 1e-3


def test_pad_tokens_are_routed_and_not_counted(params):
    mlp = _layer_mlp(params)
    u = jax.random.normal(jax.random.PRNGKey(10), (6, CFG.d_model))
    valid = jnp.asarray([True, False, True, True, False, True])
    y_all, r_all = transformer.moe_dropless(u, mlp, CFG)
    y, r = transformer.moe_dropless(u, mlp, CFG, token_valid=valid)
    assert bool((y == y_all).all())
    assert int(r.sum()) == 8 and int(r_all.sum()) == 12


def test_the_whole_stack_read_is_the_layers_slice(params):
    """An expert layer reading its experts where they lie in the stacks of
    every expert layer (the decode step's way) computes what it computes
    from its own slice."""
    stacked = params["layers"]["mlp"]
    u = jax.random.normal(jax.random.PRNGKey(11), (9, CFG.d_model))
    for layer in range(CFG.n_layers - CFG.first_k_dense):
        own = jax.tree.map(lambda w: w[layer], stacked)
        whole = {**own, **{k: stacked[k] for k in ("gate", "up", "down")}}
        a, ra = transformer.moe_dropless(u, own, CFG)
        b, rb = transformer.moe_dropless(u, whole, CFG, layer=layer)
        assert float(jnp.abs(a - b).max()) < 1e-6
        assert ra.tolist() == rb.tolist()


def test_the_rotary_layout_the_program_holds_is_exact():
    """De-interleaved columns under the half-split rotary give the
    interleaved rotary's scores at every pair of positions, bit for bit up
    to float32 rounding: the permutation moves q and k alike."""
    q_cols, kv_cols = ref.rope_columns(W)
    nope, rope = W.qk_nope_head_dim, W.qk_rope_head_dim
    assert sorted(q_cols.tolist()) == list(range(
        W.num_attention_heads * (nope + rope)))
    assert kv_cols[:W.kv_lora_rank].tolist() == list(range(W.kv_lora_rank))
    key = jax.random.PRNGKey(12)
    t = 24
    q = jax.random.normal(key, (t, rope))
    k = jax.random.normal(jax.random.fold_in(key, 1), (t, rope))
    want = ref._rope_interleaved(q, W.rope_theta) @ ref._rope_interleaved(
        k, W.rope_theta).T
    half = kv_cols[W.kv_lora_rank:] - W.kv_lora_rank
    cos, sin = transformer.rotary_frequencies(rope, t, theta=W.rope_theta)
    rq = transformer.rotate_half(q[None, :, None, half], cos[None],
                                 sin[None])[0, :, 0]
    rk = transformer.rotate_half(k[None, :, None, half], cos[None],
                                 sin[None])[0, :, 0]
    assert float(jnp.abs(rq @ rk.T - want).max()) < 1e-4
    # Without the permutation the scores are another function.
    plain = transformer.rotate_half(q[None, :, None], cos[None], sin[None])
    assert float(jnp.abs(plain[0, :, 0] @ transformer.rotate_half(
        k[None, :, None], cos[None], sin[None])[0, :, 0].T - want).max()) > 0.1


REFUSALS = {
    "paged KV layout": {"kv_layout": "paged", "kv_block_size": 8},
    "prefix cache": {"prefix_cache_slots": 2},
    "speculative decoding": {"speculative_k": 2},
    "tensor parallelism": {"tp_shards": 2},
    "pipeline parallelism": {"pp_stages": 3},
    "host tier": {"host_kv_bytes": 1 << 20},
    "handoff": {"role": "prefill"},
    "KV economy": {"kv_directory": object()},
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_refuses_a_latent_model_by_name(params, what):
    with pytest.raises(ValueError, match=f"kv_lora_rank: .*{what}"):
        _decoder(params, **REFUSALS[what])


def test_what_a_latent_config_refuses():
    with pytest.raises(ValueError, match="come together"):
        dataclasses.replace(CFG, d_expert=0)
    with pytest.raises(ValueError, match="plain stacked block"):
        dataclasses.replace(CFG, n_passes=2)
    with pytest.raises(ValueError, match="first_k_dense 3"):
        dataclasses.replace(CFG, first_k_dense=3)
    with pytest.raises(ValueError, match="latent cache serves on"):
        decode.init_paged_state(CFG, 2, 8, 8, 4)


@pytest.mark.parametrize("mode_fault", [("int8", None), ("f32",
                                                         "softmax_routing"),
                                        ("f32", "no_routed_scaling"),
                                        ("f32", "no_shared"),
                                        ("f32", "capacity"),
                                        ("f32", "no_kv_norm")])
def test_each_planted_fault_moves_the_logits_past_the_tolerance(mode_fault):
    mode, fault = mode_fault
    tokens = np.asarray([PROMPTS[1] + PROMPTS[4][:20]])
    positions = np.arange(8, 48)[None]
    sound = ref.logits_at(SEED, W, tokens, positions)
    wrong = ref.logits_at(SEED, W, tokens, positions, mode, fault,
                          np.array([48]))
    assert float(jnp.abs(sound - wrong).max()) > 0.1
