"""The length-bounded read of the dense K/V store
(``ops/attention.py:dense_decode_attention``): the kernel in interpret
mode against what the decode step does where the kernel does not compile
(``_store_rows`` + ``_gqa_attention`` under the ``<= pos_b`` mask), and a
decode step that runs the interpreted kernel against one that does not.
On the CPU the op chooses the XLA arm, and says so."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import decode, transformer
from kubeflow_tpu.ops import attention

T, HD, CHUNK = 40, 128, 16
# (write position, whether the row emits this step) of every row: a free
# slot (reads nothing, yields zeros), one position, a chunk's edge and one
# past it, the middle of a chunk, the whole row (its last chunk starts
# early: 40 is not whole chunks of 16), a row parked at ``total`` that emits
# nothing, and one parked there that still runs the pass (the verify
# round's commit).
ROWS = [(5, False), (0, True), (CHUNK - 1, True), (CHUNK, True),
        (CHUNK + 7, True), (T - 1, True), (T, False), (T, True)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("group,hkv", [(1, 16), (4, 8), (1, 8), (4, 16)])
def test_the_kernel_reads_what_each_row_holds(group, hkv, dtype):
    rng = np.random.default_rng(3)
    b, hq = len(ROWS), group * hkv
    cfg = transformer.TransformerConfig(
        vocab_size=8, d_model=hq * HD, n_layers=1, n_heads=hq,
        n_kv_heads=hkv, d_ff=8, max_seq_len=T, dtype=dtype)
    k_store, v_store = (jnp.asarray(rng.standard_normal((3, b, T, hkv, HD)),
                                    dtype) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((b, hq, HD)), dtype)
    pos_b = jnp.asarray([p for p, _ in ROWS], jnp.int32)
    emits = jnp.asarray([e for _, e in ROWS])
    lengths = jnp.where(emits, pos_b + 1, 0)

    @jax.jit
    def kernel(li):  # the layer index traced, the middle of three
        return attention._dense_decode_pallas(
            q, k_store, v_store, li, lengths, chunk=CHUNK, interpret=True)

    @jax.jit
    def step_reads(li):
        valid = jnp.arange(T)[None, :] <= pos_b[:, None]
        return decode._gqa_attention(
            q[:, None], decode._store_rows(k_store, li, None),
            decode._store_rows(v_store, li, None),
            valid[:, None, None, None, :], cfg).reshape(b, hq, HD)

    got = np.asarray(kernel(jnp.int32(1)), np.float32)
    want = np.asarray(step_reads(jnp.int32(1)), np.float32)
    live = np.asarray(emits)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    assert not got[~live].any()
    # The op's own XLA arm is the same read, and another layer is not.
    xla = np.asarray(attention.dense_decode_attention(
        q, k_store, v_store, jnp.int32(1), lengths, n_kv_heads=hkv,
        implementation="xla"), np.float32)
    np.testing.assert_allclose(xla, got, rtol=tol, atol=tol)
    other = np.asarray(kernel(jnp.int32(2)), np.float32)
    assert np.abs(other[live] - got[live]).max() > 0.1


@pytest.mark.parametrize("width", [100, 300])
def test_the_op_at_the_chunk_it_ships_with(width):
    """Rows narrower than one chunk of 128, and rows of two chunks and a
    last one that starts early."""
    rng = np.random.default_rng(4)
    k_store, v_store = (jnp.asarray(
        rng.standard_normal((2, 3, width, 8, 128)), jnp.float32)
        for _ in range(2))
    q = jnp.asarray(rng.standard_normal((3, 8, 128)), jnp.float32)
    lengths = jnp.asarray([width, 0, width - 63], jnp.int32)
    got, want = (attention.dense_decode_attention(
        q, k_store, v_store, 1, lengths, n_kv_heads=8, **how)
        for how in ({"implementation": "pallas", "interpret": True},
                    {"implementation": "xla"}))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("preset", ["lm-test-tiny", "ouro-test-tiny"])
def test_a_decode_step_on_the_kernel_emits_the_xla_steps_tokens(
        preset, monkeypatch):
    """24 steps of ``decode_step`` over a dense cache filled with random
    K/V, rows at unequal lengths, one free and one that runs out of row:
    the same tokens whichever arm reads. The choice is the op's; the test
    steers it where the program asks (no argument of ``decode_step``)."""
    cfg = transformer.config(preset, dtype=jnp.float32, max_seq_len=48)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    slots, total, steps = 4, 48, 24

    def run():
        state = decode.init_decode_state(cfg, slots, total)
        fill, rng = np.random.default_rng(5), np.random.default_rng(11)
        state["cache"] = jax.tree.map(
            lambda a: jnp.asarray(fill.standard_normal(a.shape), a.dtype),
            state["cache"])
        lengths = jnp.asarray([3, 17, total, 30], jnp.int32)
        state = {**state, "length": lengths,
                 "remaining": jnp.asarray([30, 30, 0, 30], jnp.int32),
                 "active": jnp.asarray([True, True, False, True]),
                 "last_logits": jnp.asarray(
                     rng.standard_normal((slots, cfg.vocab_size)),
                     jnp.float32)}
        out = []
        decode.decode_step.clear_cache()
        for _ in range(steps):
            state, tok, emit = decode.decode_step(state, params, cfg)
            out.append((np.asarray(tok), np.asarray(emit)))
        decode.decode_step.clear_cache()
        return out

    want = run()
    calls = []

    def interpreted(*args, **kw):
        calls.append(args[0].shape)
        return attention.dense_decode_attention(
            *args, **kw, implementation="pallas", interpret=True)

    monkeypatch.setattr(decode, "dense_decode_implementation",
                        lambda head_dim, dtype: "pallas")
    monkeypatch.setattr(decode, "dense_decode_attention", interpreted)
    got = run()
    assert calls and calls[0] == (slots, cfg.n_heads, cfg.head_dim)
    emitted = 0
    for (tok, emit), (tok_x, emit_x) in zip(got, want, strict=True):
        np.testing.assert_array_equal(emit, emit_x)
        np.testing.assert_array_equal(tok[emit], tok_x[emit_x])
        emitted += int(emit.sum())
    assert emitted == 24 + 24 + 18  # row 3 runs out of row after 18


def test_off_the_tpu_the_op_chooses_the_xla_read():
    store = jnp.zeros((2, 1, 8, 2, 128))
    args = (jnp.zeros((1, 4, 128)), store, store, 0,
            jnp.ones((1,), jnp.int32))
    assert attention.dense_decode_implementation(128, jnp.bfloat16) == "xla"
    assert attention.dense_decode_attention(
        *args, n_kv_heads=2).shape == (1, 4, 128)
    with pytest.raises(ValueError, match="implementation='pallas'"):
        attention.dense_decode_attention(*args, n_kv_heads=2,
                                         implementation="pallas")
    with pytest.raises(ValueError, match="unknown implementation"):
        attention.dense_decode_attention(*args, n_kv_heads=2,
                                         implementation="triton")
    with pytest.raises(ValueError, match="not a multiple of kv heads"):
        attention.dense_decode_attention(*args, n_kv_heads=3)


def test_on_a_tpu_the_choice_goes_by_head_size_and_dtype(monkeypatch):
    monkeypatch.setattr(attention, "not_tpu", lambda: None)
    choose = attention.dense_decode_implementation
    assert choose(128, jnp.bfloat16) == "pallas"
    assert choose(256, jnp.float32) == "pallas"
    assert choose(64, jnp.bfloat16) == "xla"
    assert choose(128, jnp.int8) == "xla"


def test_under_a_mesh_the_xla_read_stays(monkeypatch):
    """GSPMD cannot partition a Mosaic call: a sharded decoder hands its
    mesh to every step that reads the dense cache, and the read stays
    XLA's there, whatever the backend and the heads."""
    from kubeflow_tpu.parallel.mesh import serving_mesh
    from kubeflow_tpu.serving import continuous

    def not_under_a_mesh(*_a, **_k):
        raise AssertionError("the kernel was asked for")

    cfg = transformer.config("lm-test-tiny")
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    for module in (decode, continuous):
        monkeypatch.setattr(module, "dense_decode_implementation",
                            lambda head_dim, dtype: "pallas")
    monkeypatch.setattr(decode, "dense_decode_attention", not_under_a_mesh)
    state = decode.init_decode_state(cfg, 2, 16)
    slots, toks = jnp.zeros((1,), jnp.int32), jnp.zeros((1, 8), jnp.int32)
    one, temp = jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.float32)
    decode.decode_step.lower(state, params, cfg, mesh=serving_mesh(2))
    decode.admit_rows_and_step.lower(state, params, cfg, slots, toks, one,
                                     one, temp, mesh=serving_mesh(2))
    with pytest.raises(AssertionError, match="the kernel was asked for"):
        decode.decode_step.lower(state, params, cfg)
    with pytest.raises(AssertionError, match="the kernel was asked for"):
        decode.admit_rows_and_step.lower(state, params, cfg, slots, toks,
                                         one, one, temp)
    alone = continuous.ContinuousDecoder(params, cfg, slots=2,
                                         prefill_len=8, max_new_tokens=4)
    sharded = continuous.ContinuousDecoder(params, cfg, slots=2,
                                           prefill_len=8, max_new_tokens=4,
                                           tp_shards=2)
    try:
        assert alone.dense_attn_impl == "pallas" and alone._kmesh is None
        assert sharded.dense_attn_impl == "xla"
        assert sharded._kmesh is sharded.mesh is not None
    finally:
        alone.stop()
        sharded.stop()


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_the_decoder_and_monitoring_say_which_read_it_compiled(layout):
    import http.client

    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.server import ModelServer

    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=2, max_seq_len=32,
                     max_new_tokens=4, kv_layout=layout, kv_block_size=4),
        port=0, grpc_port=None, batch_timeout_ms=2)
    server.start()
    try:
        server.handle_predict("lm-test-tiny", {
            "instances": [{"tokens": [1, 2, 3], "max_new_tokens": 2}]})
        metrics = server.decoder.metrics()
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        conn.request("GET", "/monitoring/prometheus/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
    finally:
        server.stop()
    # The CPU: the XLA read where the cache is dense, none where it is a pool.
    assert metrics["dense_attn_impl"] == ("xla" if layout == "dense" else "")
    assert "serving_dense_attn_pallas 0" in text
