"""Kernels of the serving path compiled for a v5e that is described, not
attached: what Mosaic refuses (a misaligned slice, too much VMEM) shows up
here, where interpret mode shows nothing. Nothing runs, so nothing here is
a result or a time. The topology is described inside a fixture of this one
file, never at import: one process at a time may load the TPU's library,
and every xdist worker imports every test file."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kubeflow_tpu.ops import sparse_attention as sa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture()
def arg(one_chip):
    """A described argument: a shape and dtype placed on the one chip."""
    def described(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return described


# (rows, pool blocks, block, blocks a row, slots a (row, head), blocks a
# chunk): the long-decode cell's shapes as the decode step calls the
# kernel (its block table and selection, 214 KB, fit scalar memory), and
# a small block with a chunk that does not divide the slots.
SHAPES = {"long-decode": (64, 18688, 64, 584, 128, None),
          "block-8-ragged-chunk": (8, 512, 8, 64, 20, 8)}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_sparse_decode_kernel_compiles_for_v5e(arg, no_compile_cache,
                                                   shape):
    b, n_pool, bs, mb, n, chunk = SHAPES[shape]
    hkv, group, hd = 2, 16, 128

    def read(q, pool_k, pool_v, table, idx, count, cut, keep):
        kw = {} if chunk is None else {"blocks_per_chunk": chunk}
        return sa._attend_pool_pallas(q, pool_k, pool_v, 1, table, idx, count,
                                      cut, keep, **kw)

    pool = arg((2, n_pool, hkv, bs, hd), jnp.bfloat16)
    compiled = jax.jit(read).lower(
        arg((b, hkv, group, hd), jnp.bfloat16), pool, pool,
        arg((b, mb), jnp.int32), arg((b, hkv, n), jnp.int32),
        arg((b, hkv), jnp.int32),
        arg((b, hkv), jnp.int32), arg((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The pools go in whole and in place: nothing of their size is made.
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# The selection as `long-decode` calls it: a decode step's 64 rows of one
# query, and a prefill chunk's block of 128 queries of one row, over 584
# blocks' compressed keys (MiniCPM-SALA's sparse_config), two KV heads.
# (rows, queries, most temporaries in MB: a query block has its float32
# scores over the compressed keys, 48 MB; a decode step has none.)
SELECT_CALLS = {"decode-step": (64, 1, 1), "prefill-query-block": (1, 128, 64)}


@pytest.mark.parametrize("call", sorted(SELECT_CALLS))
def test_the_block_selection_sorts_down_the_sublanes_on_v5e(
        arg, no_compile_cache, call):
    """On the chip a sort of ``f32[64,2,1,584]`` along its lanes, one row a
    tile, took 0.32-0.63 ms of `long-decode`'s step, twice; the same rows
    as ``f32[128,584]``, the rows along the lanes and each sorted down the
    sublanes (layout ``{0,1}``), under 0.1 (PERF.md, PR 35)."""
    rows, queries, temp_mb = SELECT_CALLS[call]
    spec = sa.SparseSpec(kernel=32, stride=16, block=64, topk=64,
                         init_blocks=1, window=2048, dense_len=8192)
    n_blocks, hkv = 584, 2

    compiled = jax.jit(
        lambda q, ckeys, pos: sa.select_blocks(q, ckeys, pos, n_blocks, spec)
    ).lower(arg((rows, hkv, 16, queries, 128), jnp.bfloat16),
            arg((rows, hkv, spec.n_windows(n_blocks * 64), 128),
                jnp.bfloat16),
            arg((rows, queries), jnp.int32)).compile()
    sorts = re.findall(r"= \((f32\[[\d,]+\]\{[\d,]+)\S*, s32\S+ sort\(",
                       compiled.as_text())
    assert sorts == [f"f32[{rows * hkv * queries},{n_blocks}]{{0,1"], sorts
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mb << 20


# `chat-steady`'s decode step: 6 Mistral-7B layers held at bf16, 32 rows of
# 768 (dense), or the same tokens in a pool of 16-token blocks. The most
# temporaries the step may have, in MB, whatever the layout and the read.
# A K/V store that rides the layer loop as a scanned input and output, not
# as its carry, costs a second whole store (605.7 MB dense, 739.7 paged,
# 606.5 fused, at PR 31) and a copy of it every step. In place: 1.6, 34.4
# and 52.3 MB (the fused kernel still relays one layer's pool head-major
# for its tiles).
DECODE_STEP_TEMP_MB = 64
# `reason-decode`'s: Ouro-2.6B whole, 192 cache layers of 4 rows of 1,280.
# Its step's temporaries are three whole-stack transposed copies of wq, wk
# and wv, 1,208.9 MB (PERF.md, PR 36); the dense read's kernel adds none.
OURO_STEP_TEMP_MB = 1210


@pytest.mark.parametrize("layout", ["dense", "dense-ouro-2.6b", "paged",
                                    "paged-kv_fused"])
def test_the_decode_step_holds_no_second_cache_on_v5e(one_chip,
                                                      no_compile_cache,
                                                      monkeypatch, layout):
    from kubeflow_tpu.models import decode, transformer
    from kubeflow_tpu.ops import attention

    cfg = transformer.TransformerConfig(
        vocab_size=32768, d_model=4096, n_layers=6, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=768, rope_theta=1e6, dtype=jnp.bfloat16)
    slots, total, most_temp_mb = 32, 768, DECODE_STEP_TEMP_MB
    if layout == "dense-ouro-2.6b":
        cfg = transformer.config("ouro-2.6b", dtype=jnp.bfloat16,
                                 max_seq_len=1280)
        slots, total, most_temp_mb = 4, 1280, OURO_STEP_TEMP_MB
    if layout.startswith("dense"):
        # The process's backend is the CPU, whatever is compiled for: the
        # dense read asks, and on the chip the answer is the kernel.
        monkeypatch.setattr(attention, "not_tpu", lambda: None)

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda: transformer.serving_params(
            transformer.init(jax.random.PRNGKey(0), cfg), cfg)))
    if layout.startswith("dense"):
        state = jax.eval_shape(
            lambda: decode.init_decode_state(cfg, slots, total))
        store = state["cache"]["k"]
    else:
        state = jax.eval_shape(
            lambda: decode.init_paged_state(cfg, 32, 1536, 16, 48))
        store = state["pool"]["k"]
    compiled = decode.decode_step.lower(
        described(state), params, cfg,
        kv_fused=layout.endswith("kv_fused")).compile()
    text = compiled.as_text()
    whole = "bf16[" + ",".join(map(str, store.shape)) + "]"
    copies = [line.strip()[:120] for line in text.splitlines()
              if re.search(re.escape(whole) + r"\S* copy\(", line)]
    assert not copies, copies
    temp_mb = compiled.memory_analysis().temp_size_in_bytes / 1e6
    assert temp_mb < most_temp_mb, temp_mb
    # The dense read is the length-bounded kernel, handed both stores whole
    # (one call in the text: the layer loop's body); no other layout has it.
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "dense_decode_attention" in line]
    assert len(calls) == (1 if layout.startswith("dense") else 0), calls
    for line in calls:
        layouts = line[line.index("operand_layout_constraints="):]
        assert layouts[:layouts.index("}}")].count(whole) == 2, layouts[:300]
