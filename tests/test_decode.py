"""KV-cache decode correctness: the scanned incremental path must match the
full re-forward at every step (tiny model, CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import transformer
from kubeflow_tpu.models.decode import generate
from kubeflow_tpu.serving.engine import EngineConfig, InferenceEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = transformer.config("lm-test-tiny")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def greedy_reference(params, cfg, prompt, steps):
    """Decode by re-running the full forward each step (no cache)."""
    toks = list(prompt)
    for _ in range(steps):
        logits = transformer.apply(
            params, jnp.asarray([toks], jnp.int32), cfg
        )
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_generate_matches_full_forward(tiny):
    cfg, params = tiny
    prompt = [5, 17, 42, 7]
    steps = 6
    toks, last = generate(
        params, jnp.asarray([prompt], jnp.int32), jnp.asarray([4]),
        cfg, max_new_tokens=steps, key=jax.random.PRNGKey(1),
        temperature=jnp.zeros((1,)),
    )
    assert toks.shape == (1, steps)
    ref = greedy_reference(params, cfg, prompt, steps)
    assert toks[0].tolist() == ref
    # Prefill logits equal the full forward's last-position logits.
    full = transformer.apply(params, jnp.asarray([prompt], jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(last[0]),
                               np.asarray(full[0, -1], np.float32),
                               rtol=2e-2, atol=2e-2)


def test_generate_ragged_batch_padding_invariance(tiny):
    """A short prompt decodes the same whether batched with a longer one
    (per-row positions + validity masking) or alone."""
    cfg, params = tiny
    short, long_ = [9, 3], [5, 17, 42, 7, 23, 11]
    prompts = np.zeros((2, 6), np.int32)
    prompts[0, :2] = short
    prompts[1, :] = long_
    toks, _ = generate(
        params, jnp.asarray(prompts), jnp.asarray([2, 6]), cfg,
        max_new_tokens=4, key=jax.random.PRNGKey(2),
        temperature=jnp.zeros((2,)),
    )
    assert toks[0].tolist() == greedy_reference(params, cfg, short, 4)
    assert toks[1].tolist() == greedy_reference(params, cfg, long_, 4)


def test_generate_sampling_and_top_k(tiny):
    cfg, params = tiny
    prompt = jnp.asarray([[5, 17, 42]], jnp.int32)
    toks, _ = generate(
        params, prompt, jnp.asarray([3]), cfg, max_new_tokens=8,
        key=jax.random.PRNGKey(3), temperature=jnp.asarray([1.5]), top_k=10,
    )
    assert toks.shape == (1, 8)
    assert ((toks >= 0) & (toks < cfg.vocab_size)).all()


def test_engine_generate_instances():
    eng = InferenceEngine(EngineConfig(model="lm-test-tiny", batch_size=4,
                                       max_seq_len=32, max_new_tokens=8))
    out = eng.predict_batch([
        {"tokens": [1, 2, 3], "max_new_tokens": 5, "return_logits": True},
        {"tokens": [7, 8], "max_new_tokens": 2, "temperature": 0.7},
        {"tokens": [4, 4, 4]},  # plain predict rides the same batch
    ])
    assert len(out[0]["tokens"]) == 5
    assert len(out[1]["tokens"]) == 2
    assert out[2]["tokens"] == []
    assert isinstance(out[2]["next_token"], int)
    # Full-vocab logits only on request (JSON size) or for plain predicts.
    assert "logits" not in out[1]
    assert "logits" in out[2]
    # Greedy generation is the argmax continuation.
    assert out[0]["next_token"] == int(np.argmax(out[0]["logits"]))
    # Over-limit request rejected at validation.
    with pytest.raises(ValueError):
        eng.validate_instance({"tokens": [1], "max_new_tokens": 99})


# ---------------------------------------------------------------------------
# The layer loop carries the K/V storage whole and writes it in place. Its
# reference is what it replaced, kept only here: a plain Python loop over
# layers that takes layer l's slice out, runs the same attention on it, and
# writes the slice back.
# ---------------------------------------------------------------------------

SLOTS, BLOCK, BLOCKS_A_ROW = 4, 4, 6
TOTAL = BLOCK * BLOCKS_A_ROW
LAYOUTS = {"dense": {}, "paged-fp": {"kv_dtype": "fp"},
           "paged-int8": {"kv_dtype": "int8"},
           "paged-fused": {"kv_dtype": "fp", "fused": True}}


def _filled_state(cfg, layout, lengths):
    """A decode state whose storage holds random K/V everywhere (so a write
    that lands in the wrong place, or a read of the wrong layer, shows)."""
    from kubeflow_tpu.models import decode as D

    kw = dict(LAYOUTS[layout])
    kw.pop("fused", None)
    rng = np.random.RandomState(7)
    if layout == "dense":
        state = D.init_decode_state(cfg, SLOTS, TOTAL)
        name = "cache"
    else:
        state = D.init_paged_state(cfg, SLOTS, SLOTS * BLOCKS_A_ROW + 3,
                                   BLOCK, BLOCKS_A_ROW, **kw)
        name = "pool"
        # Rows own shuffled blocks; the last entry of row 0 stays the
        # unallocated sentinel.
        ids = rng.permutation(SLOTS * BLOCKS_A_ROW).reshape(
            SLOTS, BLOCKS_A_ROW).astype(np.int32)
        ids[0, -1] = SLOTS * BLOCKS_A_ROW + 3
        state["block_table"] = jnp.asarray(ids)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.randint(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.rand(*a.shape) + 0.01, a.dtype)

    state[name] = jax.tree.map(fill, state[name])
    lengths = jnp.asarray(lengths, jnp.int32)
    return {**state,
            "length": lengths,
            "remaining": jnp.full((SLOTS,), 8, jnp.int32),
            "active": lengths < TOTAL,
            "last_logits": jnp.asarray(
                rng.randn(SLOTS, cfg.vocab_size), jnp.float32)}


def _slice_loop_forward(params, cfg, k, v, tok, pos_b, live, table, fused):
    """The parent's semantics: per layer, slice ``store[l]`` out, attend
    over the slice with the SAME attention, write the slice back."""
    from kubeflow_tpu.models import decode as D
    from kubeflow_tpu.ops import rms_norm
    from kubeflow_tpu.ops.rotary import rotary_frequencies

    cos_t, sin_t = rotary_frequencies(cfg.head_dim, TOTAL,
                                      theta=cfg.rope_theta)
    rope_bt = (cos_t[pos_b[:, None]], sin_t[pos_b[:, None]])
    valid = jnp.arange(TOTAL)[None, :] <= pos_b[:, None]
    x = D._embed(params, tok, cfg)[:, None]
    for l in range(cfg.n_layers):
        layer = jax.tree.map(lambda a: a[l], params["layers"])
        k_l = jax.tree.map(lambda a: a[l][None], k)
        v_l = jax.tree.map(lambda a: a[l][None], v)
        h = rms_norm(x, layer["ln_attn"], eps=cfg.norm_eps)
        attn, k_l, v_l = D._ragged_attention(
            h, layer["attn"], cfg, rope_bt, k_l, v_l, 0, pos_b, valid, live,
            table=table, fused=fused)
        k = jax.tree.map(lambda a, s: a.at[l].set(s[0]), k, k_l)
        v = jax.tree.map(lambda a, s: a.at[l].set(s[0]), v, v_l)
        x = x + attn
        h = rms_norm(x, layer["ln_mlp"], eps=cfg.norm_eps)
        x = x + D._ffn(h, layer["mlp"], cfg, live[:, None, None])
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    return D._head(params, x, cfg)[:, 0], k, v


def _same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# What the rows are doing: all mid-row; one parked at ``total`` (a
# retired row: it must write nowhere); two steps through decode_chunk.
CASES = {"step": [3, 9, 0, TOTAL - 1], "parked": [5, TOTAL, 2, TOTAL],
         "chunk-of-2": [3, 9, 0, TOTAL - 2]}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_carried_layer_loop_equals_the_slice_and_write_back_loop(
        tiny, layout, case):
    from kubeflow_tpu.models import decode as D

    # float32 end to end: XLA's CPU backend rounds a fused bf16 chain less
    # often than an unfused one, and the two loops fuse differently.
    cfg = transformer.config("lm-test-tiny", dtype=jnp.float32)
    params = tiny[1]
    fused = LAYOUTS[layout].get("fused", False)
    state = _filled_state(cfg, layout, CASES[case])
    k, v, table, total = D._state_kv(state)
    assert total == TOTAL
    before = jax.tree.map(np.asarray, (k, v))

    steps = 2 if case == "chunk-of-2" else 1
    ref = jax.jit(_slice_loop_forward, static_argnums=(1, 8))
    length, live, last = state["length"], state["active"], state["last_logits"]
    want_toks = []
    for _ in range(steps):
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        logits, k, v = ref(params, cfg, k, v, tok, length, live, table, fused)
        last = jnp.where(live[:, None], logits, last)
        length = length + live
        want_toks.append(tok)
        live = live & (length < TOTAL)
    want_kv = jax.tree.map(np.asarray, (k, v))

    if steps == 1:
        got, tok, _emit = D.decode_step(state, params, cfg, kv_fused=fused)
        toks = tok[None]
    else:
        got, toks, _emits = D.decode_chunk(state, params, cfg, steps,
                                           kv_fused=fused)
    _same(toks, jnp.stack(want_toks))
    _same(got["last_logits"], last)
    _same(D._state_kv(got)[:2], want_kv)
    _same(got["length"], length)

    if case == "parked":
        # Parked rows wrote nowhere: what rows 1 and 3 held is as it was
        # (dense: their own rows; paged: the blocks their tables name).
        for side_got, side_before in zip(D._state_kv(got)[:2], before):
            for g, b in zip(jax.tree.leaves(side_got),
                            jax.tree.leaves(side_before), strict=True):
                g = np.asarray(g)
                for row in (1, 3):
                    own = (row if table is None
                           else np.asarray(got["block_table"])[row])
                    np.testing.assert_array_equal(g[:, own], b[:, own])
