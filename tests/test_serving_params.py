"""Serving holds its weights at the compute dtype: ``serving_params`` casts
exactly the leaves ``cast_param`` is applied to, once, where a tree becomes a
serving tree (engine load, decoder construction, the draft model), a pushed
or pulled tree lands at the held dtype, and no decode or admission dispatch
converts a parameter again. Same rounding in another place, so every
comparison here is exact. It also holds the stacked q/k/v transposed,
``w?_t`` [L, N, D], the layout the decode dot reads (a mixer model's
per-layer trees keep their names); the engine keeps the checkpoint's names
until a decoder takes its tree."""

from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import decode
from kubeflow_tpu.models import transformer
from kubeflow_tpu.models.transformer import serving_params
from kubeflow_tpu.parallel.sharding import path_str
from kubeflow_tpu.serving import weights as weights_mod
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.engine import EngineConfig, InferenceEngine
from kubeflow_tpu.serving.server import ModelServer

CONFIGS = {
    "dense": transformer.config("lm-test-tiny"),
    "moe": transformer.config("moe-test-tiny"),
    "tied": transformer.config("lm-test-tiny", tie_embeddings=True),
    "float32": transformer.config("lm-test-tiny", dtype=jnp.float32),
    "hybrid": transformer.config("sala-test-tiny"),
}
QKV = ("wq", "wk", "wv")
CFG = CONFIGS["dense"]
PROMPTS = [[3 + (j % 23) for j in range(12)], [9, 8, 7, 6, 5], [40, 41]]
GEN = 12


def _init(cfg, seed=0):
    return transformer.init(jax.random.PRNGKey(seed), cfg)


def _paths(tree) -> dict:
    return weights_mod.flatten_params(tree)


def _is_matrix(path: str) -> bool:
    """A leaf the forward casts to cfg.dtype (the router and the norm
    gains are used in float32)."""
    leaf = path.rsplit("/", 1)[-1].removesuffix("_t")
    return leaf in ("kernel", "wq", "wk", "wv", "wo", "wg", "gate", "up",
                    "down")


def _objects(tree) -> dict:
    return {path_str(kp): leaf
            for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _as_held(tree) -> dict:
    """``tree``'s leaves under a serving tree's names and layout."""
    out = {}
    for path, leaf in _paths(tree).items():
        if path.rsplit("/", 1)[-1] in QKV and path.startswith("layers/attn/"):
            path, leaf = path + "_t", np.swapaxes(leaf, 1, 2)
        out[path] = leaf
    return out


def _tokens(params, cfg=CFG, **kw):
    d = ContinuousDecoder(params, cfg, slots=4, prefill_len=32,
                          max_new_tokens=GEN, stream_timeout_s=120.0, **kw)
    try:
        return [d.generate(list(p), GEN, timeout=120)["tokens"]
                for p in PROMPTS]
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# (a) the helper
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", list(CONFIGS))
def test_serving_params_casts_the_leaves_cast_param_does(name):
    cfg = CONFIGS[name]
    params = _init(cfg)
    held = serving_params(params, cfg)
    was, now = _as_held(params), _paths(held)
    assert now.keys() == was.keys()
    assert ("lm_head/kernel" in was) == (name != "tied")
    assert ("layers/mlp/router" in was) == (name == "moe")
    # The stacked q/k/v under their new names only, [L, N, D]; a mixer
    # model's per-layer trees keep theirs.
    assert not {f"layers/attn/{w}" for w in QKV} & now.keys()
    stacked = name != "hybrid"
    for w, width in zip(QKV, (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)):
        assert (f"layers/attn/{w}_t" in now) == stacked
        if stacked:
            assert now[f"layers/attn/{w}_t"].shape == (
                cfg.n_layers, width * cfg.head_dim, cfg.d_model)
        else:
            assert f"layers/0/mixer/{w}" in now
    for path, leaf in now.items():
        want = jnp.dtype(cfg.dtype) if _is_matrix(path) else jnp.float32
        assert leaf.dtype == want, path
        # The rounding the step itself applied: nothing else moved.
        np.testing.assert_array_equal(
            leaf, np.asarray(jnp.asarray(was[path]).astype(want)), path)
    if name in ("float32", "hybrid"):
        # Nothing to cast: only the transposed q/k/v are new objects.
        flat = _objects(params)
        assert all(leaf is flat[path] for path, leaf in
                   _objects(held).items() if not path.endswith("_t"))
    # A tree cast earlier passes through: the same leaf objects back.
    again = serving_params(held, cfg)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(held)))


def test_serving_params_casts_host_leaves_too():
    """A peer pull hands host arrays: same numbers, cast where they are."""
    host = jax.tree.map(np.asarray, _init(CFG))
    held = serving_params(host, CFG)
    for a, b in zip(jax.tree.leaves(held),
                    jax.tree.leaves(serving_params(_init(CFG), CFG))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# (b) no dispatch converts a parameter
# ---------------------------------------------------------------------------

_MOVES = {"slice", "dynamic_slice", "squeeze", "reshape", "transpose",
          "broadcast_in_dim", "copy"}


def _sub_jaxprs(eqn):
    """(jaxpr, the eqn's operands that its invars stand for) for every
    jaxpr an equation carries."""
    name = eqn.primitive.name
    if name == "while":
        nc, nb = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
        ops = list(eqn.invars)
        return [(eqn.params["cond_jaxpr"].jaxpr, ops[:nc] + ops[nc + nb:]),
                (eqn.params["body_jaxpr"].jaxpr, ops[nc:])]
    if name == "cond":
        return [(b.jaxpr, list(eqn.invars[1:]))
                for b in eqn.params["branches"]]
    found = []
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else [value]:
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                assert len(inner.invars) == len(eqn.invars), name
                found.append((inner, list(eqn.invars)))
    return found


def _param_converts(jaxpr, fed) -> list:
    """Every ``convert_element_type`` whose operand is a parameter input,
    followed through calls, loops and pure data movement."""
    fed = set(fed)
    found = []
    for eqn in jaxpr.eqns:
        ops = [v for v in eqn.invars if not hasattr(v, "val")]
        if not any(v in fed for v in ops):
            continue
        if eqn.primitive.name == "convert_element_type":
            found.append((eqn.invars[0].aval, eqn.params["new_dtype"]))
        elif eqn.primitive.name in _MOVES:
            fed.update(eqn.outvars)
        for inner, operands in _sub_jaxprs(eqn):
            found += _param_converts(
                inner, [iv for iv, op in zip(inner.invars, operands)
                        if not hasattr(op, "val") and op in fed])
    return found


def _decode_step(state, params):
    return decode.decode_step(state, params, CFG)


def _admit_rows(state, params):
    return decode.admit_rows_and_step(
        state, params, CFG, jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1,), jnp.int32),
        jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.float32))


@pytest.mark.parametrize("dispatch", [_decode_step, _admit_rows],
                         ids=["decode_step", "admit_rows_and_step"])
def test_no_dispatch_converts_a_parameter_of_a_serving_tree(dispatch):
    state = decode.init_decode_state(CFG, 2, 24)

    def converts(params):
        closed = jax.make_jaxpr(dispatch)(state, params)
        n_state = len(jax.tree.leaves(state))
        return _param_converts(closed.jaxpr, closed.jaxpr.invars[n_state:])

    # On the float32 tree every matrix is converted (so this can fail):
    # embed, head, four attention and three MLP stacks at the least.
    on_float32 = converts(_init(CFG))
    assert len(on_float32) >= 9
    assert all(new == jnp.bfloat16 for _, new in on_float32)
    assert converts(serving_params(_init(CFG), CFG)) == []


# ---------------------------------------------------------------------------
# (c) bit for bit what the float32 tree gave
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["dense", "moe", "tied"])
def test_generate_logits_and_tokens_are_bit_identical(name):
    cfg = CONFIGS[name]
    params = _init(cfg, seed=3)
    prompts = jnp.asarray([[5, 6, 7, 8, 9, 10, 0, 0], [11, 12, 13, 0, 0, 0,
                                                       0, 0]], jnp.int32)
    lengths = jnp.asarray([6, 3], jnp.int32)

    def run(tree):
        return decode.generate(
            tree, prompts, lengths, cfg, max_new_tokens=6,
            key=jax.random.PRNGKey(0), temperature=jnp.zeros((2,)))

    tokens, logits = run(params)
    held_tokens, held_logits = run(serving_params(params, cfg))
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(held_logits))
    np.testing.assert_array_equal(np.asarray(tokens),
                                  np.asarray(held_tokens))


@pytest.mark.parametrize("name", ["dense", "moe", "tied"])
def test_apply_gives_the_float32_trees_logits_on_its_serving_tree(name):
    """The engine's predict path (``transformer.apply``) reads either
    layout: the checkpoint's through ``@``, the serving tree's through
    the dot over both minor axes."""
    cfg = CONFIGS[name]
    params = _init(cfg, seed=4)
    tokens = jnp.asarray([[5, 6, 7, 8, 9, 10], [11, 12, 13, 1, 2, 3]],
                         jnp.int32)
    want = transformer.apply(params, tokens, cfg).astype(jnp.float32)
    got = transformer.apply(serving_params(params, cfg), tokens,
                            cfg).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(np.argmax(got, -1), np.argmax(want, -1))


@pytest.mark.parametrize("layout", [{}, {"kv_layout": "paged",
                                         "kv_block_size": 4}],
                         ids=["dense_kv", "paged_kv"])
def test_decoder_tokens_identical_from_float32_and_pre_cast_tree(layout):
    params = _init(CFG, seed=2)
    assert _tokens(params, **layout) == _tokens(
        serving_params(params, CFG), **layout)


def test_decoder_holds_no_float32_matrix_and_reports_its_bytes():
    params = _init(CFG)
    d = ContinuousDecoder(params, CFG, slots=2, prefill_len=16,
                          max_new_tokens=4)
    try:
        for path, leaf in _paths(d.params).items():
            assert leaf.dtype == (jnp.bfloat16 if _is_matrix(path)
                                  else jnp.float32), path
        m = d.metrics()
        want = sum(l.size * (2 if _is_matrix(p) else 4)
                   for p, l in _paths(params).items())
        assert m["weights_bytes"] == want
        assert m["weights_dtype"] == "bfloat16"
        assert f"serving_weights_bytes {want}" in d.registry.render()
        assert m["qkv_held_transposed"] == 1
        assert "serving_qkv_held_transposed 1" in d.registry.render()
    finally:
        d.stop()


def test_float32_compute_holds_float32_and_twice_the_matrix_bytes():
    cfg = CONFIGS["float32"]
    d = ContinuousDecoder(_init(cfg), cfg, slots=2, prefill_len=16,
                          max_new_tokens=4)
    try:
        m = d.metrics()
        assert m["weights_dtype"] == "float32"
        assert m["weights_bytes"] == sum(
            4 * l.size for l in jax.tree.leaves(d.params))
    finally:
        d.stop()


def test_draft_model_tree_is_a_serving_tree():
    from kubeflow_tpu.serving.speculative import DraftModelProposer

    draft = DraftModelProposer("lm-test-tiny", CFG.vocab_size, slots=2,
                               total_len=24, propose_steps=2)
    for path, leaf in _paths(draft.params).items():
        assert leaf.dtype == (jnp.bfloat16 if _is_matrix(path)
                              else jnp.float32), path


# ---------------------------------------------------------------------------
# (d) a float32 push into the bf16 server
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["host", "device"])
def test_float32_push_lands_at_the_held_dtype(where):
    pushed = _init(CFG, seed=1)
    if where == "host":
        pushed = jax.tree.map(np.asarray, pushed)
    d = ContinuousDecoder(_init(CFG), CFG, slots=4, prefill_len=32,
                          max_new_tokens=GEN, stream_timeout_s=120.0)
    try:
        before = d.metrics()
        assert d.update_weights(pushed) == 1
        after = d.metrics()
        # The learner's tree has the checkpoint's names; it lands in the
        # serving tree's.
        assert _paths(d.params).keys() == _as_held(pushed).keys()
        assert after["qkv_held_transposed"] == 1
        for path, leaf in _paths(d.params).items():
            assert leaf.dtype == (jnp.bfloat16 if _is_matrix(path)
                                  else jnp.float32), path
        assert after["weights_bytes"] == before["weights_bytes"]
        assert after["weights_dtype"] == "bfloat16"
        assert (f"serving_weights_bytes {after['weights_bytes']}"
                in d.registry.render())
        got = [d.generate(list(p), GEN, timeout=120)["tokens"]
               for p in PROMPTS]
    finally:
        d.stop()
    assert got == _tokens(_init(CFG, seed=1))


# ---------------------------------------------------------------------------
# (e) the engine's three birth paths
# ---------------------------------------------------------------------------


def _engine_cfg(**kw):
    return EngineConfig(model="lm-test-tiny", batch_size=2, max_seq_len=16,
                        max_new_tokens=4, **kw)


def _assert_held(params, source):
    """``params`` is ``source`` (float32) as an engine holds it: cast, in
    the checkpoint's names and layout."""
    want = _paths(serving_params(source, CFG, transpose_qkv=False))
    got = _paths(params)
    assert got.keys() == want.keys()
    for path in want:
        assert got[path].dtype == want[path].dtype, path
        np.testing.assert_array_equal(got[path], want[path], path)


def test_engine_params_at_compute_dtype_after_init():
    engine = InferenceEngine(_engine_cfg())
    assert engine.weight_pull_source == "init"
    _assert_held(engine.params, _init(CFG))


def test_engine_params_at_compute_dtype_after_checkpoint_restore(tmp_path):
    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.train import checkpoint as ckpt_lib
    from kubeflow_tpu.train.optimizers import OptimizerConfig
    from kubeflow_tpu.train.trainer import init_state

    state = init_state(jax.random.PRNGKey(0), get_model("lm-test-tiny"),
                       OptimizerConfig())
    trained = jax.tree.map(lambda w: w * 1.5, state.params)
    ckpt_lib.save(str(tmp_path), 1,
                  dataclasses.replace(state, params=trained))
    engine = InferenceEngine(_engine_cfg(checkpoint_dir=str(tmp_path)))
    assert engine.weight_pull_source == "checkpoint"
    _assert_held(engine.params, trained)


def test_engine_float32_override_keeps_float32():
    engine = InferenceEngine(_engine_cfg(dtype="float32"))
    assert all(l.dtype == jnp.float32
               for l in jax.tree.leaves(engine.params))


def test_pull_carries_the_held_dtype_and_the_newborn_answers_alike():
    donor = ModelServer(_engine_cfg(), port=0, grpc_port=None,
                        batch_timeout_ms=2)
    donor.start()
    try:
        pushed = _init(CFG, seed=5)
        weights_mod.push_weights(f"127.0.0.1:{donor.port}", "lm-test-tiny",
                                 pushed, 2, chunk_bytes=4096)
        leaves, version, _ = weights_mod.pull_weights(
            f"127.0.0.1:{donor.port}", "lm-test-tiny", timeout=30.0)
        model_leaves, _ = weights_mod.split_namespaces(leaves)
        assert version == 2
        for path, leaf in model_leaves.items():
            assert leaf.dtype == (jnp.bfloat16 if _is_matrix(path)
                                  else jnp.float32), path
        newborn = InferenceEngine(_engine_cfg(
            weight_peers=f"127.0.0.1:{donor.port}",
            weight_pull_timeout_s=30.0))
        assert newborn.weight_pull_source == "peer"
        assert newborn.boot_weights_version == 2
        _assert_held(newborn.params, pushed)
        want = donor.decoder.generate(PROMPTS[1], 4, timeout=120)["tokens"]
    finally:
        donor.stop()
    d = ContinuousDecoder(newborn.params, CFG, slots=2, prefill_len=16,
                          max_new_tokens=4)
    try:
        assert d.generate(PROMPTS[1], 4, timeout=120)["tokens"] == want
    finally:
        d.stop()


# ---------------------------------------------------------------------------
# No float32 copy of a matrix outlives the build
# ---------------------------------------------------------------------------


def test_no_float32_matrix_is_alive_once_the_decoder_is_built(monkeypatch):
    # Widths no other test of this process uses, so a live array's shape
    # says whose it is.
    cfg = transformer.config("lm-test-tiny", d_model=72, d_ff=136,
                             vocab_size=264, n_heads=4, n_kv_heads=2)
    monkeypatch.setitem(transformer.PRESETS, "lm-test-odd", cfg)
    # The matrices as the checkpoint and as the decoder hold them (wk and
    # wv transposed are shapes of their own).
    shapes = {leaf.shape for tree in (_init(cfg), serving_params(
        _init(cfg), cfg)) for kp, leaf in jax.tree_util.tree_flatten_with_path(
        tree)[0] if _is_matrix(path_str(kp))}
    assert len(shapes) >= 6

    def float32_matrices():
        gc.collect()
        return [a.shape for a in jax.live_arrays()
                if a.dtype == jnp.float32 and a.shape in shapes]

    # The loader lets each float32 leaf go as it is cast: when the k-th
    # matrix is about to be cast, the k before it are gone already, so
    # the transient is one leaf and never a second tree.
    alive_at_cast = []
    cast_param = transformer.cast_param

    def spy(w, dtype):
        if isinstance(w, jax.Array) and w.shape in shapes:
            alive_at_cast.append(len(float32_matrices()))
        return cast_param(w, dtype)

    monkeypatch.setattr(transformer, "cast_param", spy)
    server = ModelServer(
        EngineConfig(model="lm-test-odd", batch_size=2, max_seq_len=16,
                     max_new_tokens=4), port=0, grpc_port=None)
    monkeypatch.setattr(transformer, "cast_param", cast_param)
    assert alive_at_cast == list(range(9, 0, -1))
    decoder = server.decoder
    try:
        assert float32_matrices() == []
        held = [a for a in jax.live_arrays()
                if a.dtype == jnp.bfloat16 and a.shape in shapes]
        # One copy: the engine's tree IS the decoder's.
        assert len(held) == sum(_is_matrix(p) for p in _paths(
            decoder.params))
        assert all(a is b for a, b in zip(
            jax.tree.leaves(server.engine.params),
            jax.tree.leaves(decoder.params)))
        # A float32 push leaves none behind either.
        decoder.update_weights(jax.tree.map(np.asarray, _init(cfg, 1)))
        assert float32_matrices() == []
    finally:
        decoder.stop()


def test_the_relay_holds_one_stack_of_transient(monkeypatch):
    """``relay_for_decoder`` hands ``serving_params`` the engine's only
    reference: when a stacked q/k/v is transposed, the ones transposed
    before it are gone in the checkpoint's layout, so one stack of
    transient is alive beside the tree, never a second wq, wk and wv."""
    cfg = transformer.config("lm-test-tiny", d_model=80, d_ff=152,
                             vocab_size=280, n_heads=4, n_kv_heads=2)
    monkeypatch.setitem(transformer.PRESETS, "lm-test-relay", cfg)
    server = ModelServer(
        EngineConfig(model="lm-test-relay", batch_size=2, max_seq_len=16,
                     max_new_tokens=4), port=0, grpc_port=None)
    attn = server.engine.params["layers"]["attn"]
    # Every layout of a q/k/v stack (wo shares wq's shape).
    shapes = {attn[w].shape for w in QKV} | {
        attn[w].swapaxes(1, 2).shape for w in QKV}

    def stacks():
        gc.collect()
        return sum(a.dtype == jnp.bfloat16 and a.shape in shapes
                   for a in jax.live_arrays())

    before = stacks()
    alive = []
    ready = jax.block_until_ready

    def spy(x):
        alive.append(stacks())
        return ready(x)

    del attn
    monkeypatch.setattr(jax, "block_until_ready", spy)
    server.engine.relay_for_decoder()
    monkeypatch.setattr(jax, "block_until_ready", ready)
    assert alive == [before + 1] * len(QKV)
    assert stacks() == before
    assert {f"{w}_t" for w in QKV} <= server.engine.params["layers"][
        "attn"].keys()


def test_predict_compiles_once_when_the_decoder_comes_after_warm():
    """``warm`` relays the engine's tree before predict compiles, so a
    decoder built on first use (no flash-crowd surface) leaves predict's
    executable and the tree's leaves as they were."""
    server = ModelServer(_engine_cfg(), port=0, grpc_port=None,
                         batch_timeout_ms=2)
    server.start()
    try:
        engine = server.engine
        assert server._decoder is None
        assert {f"{w}_t" for w in QKV} <= engine.params["layers"][
            "attn"].keys()
        held = jax.tree.leaves(engine.params)
        compiled = engine._predict._cache_size()
        decoder = server.decoder
        for tree in (engine.params, decoder.params):
            assert all(a is b for a, b in zip(jax.tree.leaves(tree), held))
        engine.predict_batch(engine._example_instances(1))
        assert engine._predict._cache_size() == compiled
    finally:
        server.stop()
