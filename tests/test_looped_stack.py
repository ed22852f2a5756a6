"""A looped stack (``n_passes`` passes of the same layers a token, a K/V
cache of its own for every pass, sandwich norms, the final norm closing
every pass) against the plain reference ``benchmarks/reference/ouro_f32.py``
on seeded weights, at the ``ouro-test-tiny`` preset in float32: the
cache-free forward, prefill then decode through the cache on both layouts
with rows of unequal length, slots reused, every serving option that sizes
or moves K/V by layer with four passes, what refuses such a model by name,
and that each way of getting the loop wrong moves the logits by more than
the tolerance these comparisons use."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.looped import install_weights
from benchmarks.reference import ouro_f32 as ref
from kubeflow_tpu.models import decode, transformer
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.kv_allocator import kv_bytes_per_token

SEED = 5
CFG = transformer.config("ouro-test-tiny", dtype=jnp.float32)
W = ref.Widths(
    vocab_size=CFG.vocab_size, hidden_size=CFG.d_model,
    num_hidden_layers=CFG.n_layers, num_attention_heads=CFG.n_heads,
    num_key_value_heads=CFG.n_kv_heads, head_dim=CFG.head_dim,
    intermediate_size=CFG.d_ff, rope_theta=CFG.rope_theta,
    rms_norm_eps=CFG.norm_eps, total_ut_steps=CFG.n_passes,
    early_exit_threshold=CFG.exit_threshold)
# float32 against float32 at "highest" precision, summed in another order,
# through 12 layer applications and 28 norms (each divides by an RMS that
# carries the error before it): logits of standard deviation 1 agree to
# 1e-5 here; 1e-4 leaves a digit. The least of the loop's faults moves
# them by more than 1 (the last test), bfloat16 in the reference's place
# by 0.1.
TOL = 1e-4


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
    length = max(len(p) + len(o) for p, o in zip(prompts, outs))
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


def _decoder(params, cfg=CFG, **kw):
    kw = {"slots": 4, "prefill_len": 32, "max_new_tokens": 24,
          "prefill_len_buckets": 1, **kw}
    return ContinuousDecoder(params, cfg, **kw)


# Eight rows of unequal length on four slots: every slot is used a second
# time, by a request that is admitted after the first has retired.
LENGTHS = (10, 28, 5, 17, 32, 9, 21, 14)
PROMPTS = [np.random.default_rng(i).integers(0, CFG.vocab_size, n).tolist()
           for i, n in enumerate(LENGTHS)]


def _serve(params, prompts=PROMPTS, n=24, **kw):
    dec = _decoder(params, **kw)
    try:
        handles = [dec.submit(p, n) for p in prompts]
        outs = [h.result(timeout=600)["tokens"] for h in handles]
        return outs, dec.metrics()
    finally:
        dec.stop()


@pytest.fixture(scope="module")
def served_dense(params):
    return _serve(params)


def test_the_default_config_does_not_loop():
    cfg = transformer.TransformerConfig()
    assert (cfg.n_passes, cfg.post_norms, cfg.exit_threshold) == (1, False, 1.0)
    assert cfg.cache_layers == cfg.n_layers
    hybrid = transformer.config("sala-test-tiny")
    assert hybrid.cache_layers == 2  # its sparse layers alone
    assert CFG.cache_layers == CFG.n_layers * CFG.n_passes == 12


def test_kv_bytes_per_token_at_the_published_sizes():
    cfg = transformer.config("ouro-2.6b")
    assert cfg.cache_layers == 192
    assert kv_bytes_per_token(cfg.cache_layers, cfg.n_kv_heads, cfg.head_dim,
                              2) == 1_572_864
    shapes = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0),
                                                     cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 2_667_974_657
    cache = jax.eval_shape(lambda: decode.init_cache(cfg, 4, 1280))
    assert cache["k"].shape == (192, 4, 1280, 16, 128)


def test_one_pass_and_no_after_norms_is_the_stacked_path(params):
    """The tree and the logits of a config that spells the loop's fields
    at their defaults are the plain stacked model's, bit for bit."""
    plain = transformer.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, rope_theta=1e6, norm_eps=1e-6,
        remat=False, dtype=jnp.float32)
    spelled = dataclasses.replace(CFG, n_passes=1, post_norms=False)
    a = transformer.init(jax.random.PRNGKey(7), plain)
    b = transformer.init(jax.random.PRNGKey(7), spelled)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert "exit_gate" not in b and "ln_attn_post" not in b["layers"]
    assert all(bool((x == y).all()) for x, y in zip(jax.tree.leaves(a),
                                                    jax.tree.leaves(b)))
    tokens = jnp.asarray(PROMPTS[1])[None]
    assert bool((transformer.apply(a, tokens, plain)
                 == transformer.apply(b, tokens, spelled)).all())
    one = jnp.ones((1,), jnp.int32)
    admitted = [decode._admit_rows_body(
        decode.init_decode_state(cfg, 1, 40), tree, cfg, 0 * one, tokens,
        28 * one, 4 * one, jnp.zeros((1,)))
        for cfg, tree in ((plain, a), (spelled, b))]
    assert admitted[1][0]["cache"]["k"].shape[0] == 3
    assert bool((admitted[0][1] == admitted[1][1]).all())


def test_forward_without_a_cache_matches_the_reference(params):
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 48),
                                           0, CFG.vocab_size))
    positions = np.tile(np.arange(48)[None], (2, 1))
    want = ref.logits_at(SEED, W, tokens, positions)
    got = transformer.apply(params, jnp.asarray(tokens), CFG)
    assert float(jnp.abs(want - got).max()) < TOL


@pytest.mark.parametrize("layout", ["dense", "paged", "paged-int8-pool"])
def test_prefill_then_decode_logits_are_the_references(params, layout):
    """Rows of unequal length admitted in one batch, then decoded through
    the cache a token at a time: the logits the state holds after every
    step, against the reference's full forward. (An int8 pool holds the
    same 12 cache layers; its logits are the fp pool's to quantisation.)"""
    lens = np.array([16, 7, 11])
    rng = np.random.default_rng(11)
    seqs = rng.integers(0, CFG.vocab_size, (3, 16 + 6))
    if layout == "dense":
        state = decode.init_decode_state(CFG, 3, 24)
        admit = decode._admit_rows_body
    else:
        state = decode.init_paged_state(
            CFG, 3, 9, 8, 3, kv_dtype="int8" if "int8" in layout else "fp")
        state["block_table"] = jnp.arange(9, dtype=jnp.int32).reshape(3, 3)
        admit = decode._paged_admit_rows_body
    prompt = np.where(np.arange(16)[None] < lens[:, None], seqs[:, :16], 0)
    state, last = admit(state, params, CFG, jnp.arange(3),
                        jnp.asarray(prompt), jnp.asarray(lens),
                        jnp.full((3,), 6), jnp.zeros((3,)))
    got, outs = [np.asarray(last)], []
    for _ in range(5):
        state, tok, _ = decode.decode_step(state, params, CFG)
        outs.append(np.asarray(tok))
        got.append(np.asarray(state["last_logits"]))
    got, outs = np.stack(got, 1), np.stack(outs, 1)      # [3, 6, V], [3, 5]
    pool = state["pool"]["k"] if layout != "dense" else state["cache"]["k"]
    assert decode._kv_arr(pool).shape[0] == 12
    want = _reference_logits([prompt[i, :n].tolist() for i, n in
                              enumerate(lens)],
                             [outs[i].tolist() + [0] for i in range(3)])
    gap = max(float(np.abs(w - g).max()) for w, g in zip(want, got))
    # int8 K/V: one scale a (position, head), 127 levels: logits move by
    # a few hundredths, far under the loop faults' 1 and more.
    assert gap < (0.15 if "int8" in layout else TOL), gap


@pytest.mark.parametrize("row", range(len(LENGTHS)))
def test_every_served_position_is_the_references_dense(served_dense, row):
    """Through the scheduler, dense layout; rows 4-7 run on slots that
    rows 0-3 have left: all 12 cache layers of the row start clean."""
    outs, _ = served_dense
    assert len(outs[row]) == 24
    assert _widest_gap([PROMPTS[row]], [outs[row]]) < TOL


def test_the_decoder_counts_passes_and_attended_tokens(served_dense):
    outs, m = served_dense
    assert m["cache_layers"] == 12
    assert m["kv_bytes_per_token"] == 2 * 12 * CFG.n_kv_heads * CFG.head_dim * 4
    assert m["loop_passes"] == 4 * m["decode_steps"] > 0
    # Per emitted token its row's length with the token in it.
    assert m["kv_tokens_attended"] == sum(
        n + j for n in LENGTHS for j in range(1, 25))
    assert m["tokens_emitted"] == 8 * 24


def test_a_plain_model_routes_tokens_as_before(params):
    """The looped counter's routine is bound over ``_dispatch`` only where
    the stack loops: a plain model's round runs the class's own."""
    plain = transformer.config("lm-test-tiny")
    dec = _decoder(transformer.init(jax.random.PRNGKey(0), plain), plain)
    try:
        assert "_dispatch" not in vars(dec)
        out = dec.submit(PROMPTS[0], 4).result(timeout=600)["tokens"]
        m = dec.metrics()
    finally:
        dec.stop()
    assert len(out) == 4 and m["kv_tokens_attended"] == 0
    assert m["cache_layers"] == plain.n_layers
    assert m["loop_passes"] == m["decode_steps"]


# Every serving option that sizes or moves K/V by layer, with four passes:
# the tokens are the dense decoder's (greedy, float32) or, where the read
# is not bit-equal by design, within the reference's tolerance.
OPTIONS = {
    "paged": {"kv_layout": "paged", "kv_block_size": 8},
    "paged-chunked-prefill": {"kv_layout": "paged", "kv_block_size": 8,
                              "prefill_chunk_tokens": 8},
    "paged-kv_fused": {"kv_layout": "paged", "kv_block_size": 8,
                       "kv_fused": True},
    "dense-prefix-cache": {"prefix_cache_slots": 4,
                           "prefix_cache_min_len": 4,
                           "prefill_len_buckets": 3},
    "paged-prefix-cache": {"kv_layout": "paged", "kv_block_size": 8,
                           "prefix_cache_slots": 4,
                           "prefix_cache_min_len": 4,
                           "prefill_len_buckets": 3},
    "speculative": {"speculative_k": 2},
    "decode_chunk-4": {"chunk_size": 4},
    "tp_shards-2": {"tp_shards": 2, "kv_layout": "paged",
                    "kv_block_size": 8},
}


@pytest.mark.parametrize("option", sorted(OPTIONS))
def test_serving_options_hold_four_passes(params, served_dense, option):
    if option == "tp_shards-2" and len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    prompts = PROMPTS
    if "prefix" in option:
        # Two requests behind one 12-token prefix, after its first user
        # has finished and published it.
        shared = PROMPTS[0] + [3, 1]
        prompts = [shared + [5, 9, 2], shared + [8, 8, 1, 4]]
        want, _ = _serve(params, prompts)
        dec = _decoder(params, **OPTIONS[option])
        try:
            outs = [dec.submit(p, 24).result(timeout=600)["tokens"]
                    for p in prompts]
            m = dec.metrics()
        finally:
            dec.stop()
        assert m["prefix_hits"] >= 1 and m["prefix_tokens_reused"] >= 8
    else:
        want = served_dense[0]
        outs, m = _serve(params, **OPTIONS[option])
    assert m["cache_layers"] == 12
    if option == "paged-kv_fused":  # an online softmax: close, not bitwise
        assert _widest_gap(prompts, outs) < TOL
    else:
        assert outs == want
    if option == "speculative":
        assert m["spec_verify_dispatches"] > 0


def test_what_refuses_a_looped_stack_by_name(params):
    with pytest.raises(ValueError, match="exit_threshold 0.9 < 1"):
        dataclasses.replace(CFG, exit_threshold=0.9)
    with pytest.raises(ValueError, match="n_passes must be >= 1"):
        dataclasses.replace(CFG, n_passes=0)
    for field, value in (("mixer_types", ("minicpm4",) * 3),
                         ("n_experts", 4), ("context_parallel", True),
                         ("pipeline_stages", 3)):
        with pytest.raises(ValueError, match="n_passes > 1 / post_norms"):
            dataclasses.replace(CFG, **{field: value})
    paged = {"kv_layout": "paged", "kv_block_size": 8}
    refusals = {
        "pipeline parallelism": {"pp_stages": 3, **paged},
        "context parallelism": {"cp_shards": 2, "prefill_chunk_tokens": 8,
                                **paged},
        "host tier": {"host_kv_bytes": 1 << 20, **paged},
        "handoff": {"role": "prefill", **paged},
        "KV economy": {"kv_directory": object(), **paged},
    }
    for what, kw in refusals.items():
        with pytest.raises(ValueError, match=f"n_passes > 1: .*{what}"):
            _decoder(params, **kw)


@pytest.mark.parametrize("fault", ref.FAULTS[1:])
def test_each_loop_fault_moves_the_logits_past_the_tolerance(fault):
    """Three passes in place of four, one cache a layer shared by the
    passes, no norm between passes: each is far outside TOL (and outside
    bfloat16's 0.1), so these comparisons would catch it."""
    tokens = np.asarray([PROMPTS[1] + PROMPTS[4][:20]])
    positions = np.arange(28, 48)[None]  # "decoded" positions of the row
    sound = ref.logits_at(SEED, W, tokens, positions)
    wrong = ref.logits_at(SEED, W, tokens, positions, fault=fault,
                          fault_at=np.array([28]))
    assert float(jnp.abs(sound - wrong).max()) > 1.0
    if fault == "shared_cache":  # a prompt is prefilled a pass at a time
        early = np.arange(0, 28)[None]
        assert float(jnp.abs(
            ref.logits_at(SEED, W, tokens, early)
            - ref.logits_at(SEED, W, tokens, early, fault=fault,
                            fault_at=np.array([28]))).max()) == 0.0


def test_the_program_does_not_evaluate_the_exit_gate(params):
    """At threshold 1 the gate's leaves are in the tree and in no
    forward: the step's text is the same whatever they hold."""
    assert params["exit_gate"]["kernel"].shape == (CFG.d_model, 1)
    state = decode.init_decode_state(CFG, 2, 16)
    text = decode.decode_step.lower(state, params, CFG).as_text()
    jaxpr = jax.make_jaxpr(
        lambda p: decode.decode_step.__wrapped__(state, p, CFG))(params)
    used = {str(v) for eqn in jaxpr.eqns for v in eqn.invars}
    gate = [str(v) for v, leaf in zip(
        jaxpr.jaxpr.invars, jax.tree.leaves(params))
        if leaf.shape in ((CFG.d_model, 1), (1,))]
    assert len(gate) == 2 and not set(gate) & used and "while" in text
