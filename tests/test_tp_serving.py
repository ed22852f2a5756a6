"""Model-parallel serving tests: the ContinuousDecoder over a tensor
mesh.

The invariant under test everywhere: a tp-sharded replica is the SAME
engine, just spread over more chips — greedy, sampled, speculative,
prefix-hit, CoW, and int8 token streams must be byte-identical across
mesh shapes (f32 compute: the only cross-shard reductions are the
row-parallel projection psums, whose ~1e-6 reorder never flips an
argmax on these margins), the host side (allocator, trie, block ids,
handoff envelopes) must not see the split at all, and the byte gauges
must price the pool PER CHIP. Runs on the conftest 8-device CPU mesh.
"""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from kubeflow_tpu.models.registry import get_model  # noqa: E402
from kubeflow_tpu.ops.attention import (  # noqa: E402
    paged_decode_attention,
    paged_span_attention,
)
from kubeflow_tpu.parallel.mesh import (  # noqa: E402
    AXIS_TENSOR,
    serving_mesh,
)
from kubeflow_tpu.serving import handoff as handoff_mod  # noqa: E402
from kubeflow_tpu.serving.continuous import ContinuousDecoder  # noqa: E402
from kubeflow_tpu.serving.kv_allocator import (  # noqa: E402
    kv_bytes_per_token,
)

# 12 shared tokens = one full 8-token block (refcount-shared on a hit)
# plus a 4-token partial tail (one CoW per follower).
SHARED = [5, 11, 7, 3, 13, 2, 17, 9, 4, 6, 19, 8]
PROBES = ([SHARED + [23 + i, 29] for i in range(3)]
          + [[1, 2, 3], [9] * 9, list(range(4, 20))])


@pytest.fixture(scope="module")
def tiny_tp():
    # 4 kv heads so the tp=4 leg shards evenly; f32 so greedy is
    # bitwise across mesh shapes (bf16 rounds the psum partials).
    spec = get_model("lm-test-tiny", n_kv_heads=4, dtype=jnp.float32)
    return spec, spec.init(jax.random.PRNGKey(0), spec.config)


def _decoder(tiny_tp, tp=1, **kw):
    spec, params = tiny_tp
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_len", 32)
    kw.setdefault("max_new_tokens", 16)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("stream_timeout_s", 120.0)
    return ContinuousDecoder(params, spec.config, tp_shards=tp, **kw)


def _probe(d, want=6, temperature=0.0):
    return [d.generate(p, want, temperature=temperature,
                       timeout=120)["tokens"] for p in PROBES]


@pytest.fixture(scope="module")
def greedy_by_tp(tiny_tp):
    """Greedy probe streams (prefix cache on → shared-prefix probes hit
    the trie, share a full block, and CoW the tail) plus counters, per
    mesh shape — computed once, asserted by several tests."""
    out = {}
    for tp in (1, 2, 4):
        d = _decoder(tiny_tp, tp, prefix_cache_slots=4,
                     prefix_cache_min_len=4)
        try:
            toks = _probe(d)
            m = d.metrics()
            # A layer's shard of the transposed q as the mesh placed it.
            m["wq_t_shard"] = d.params["layers"]["attn"][
                "wq_t"].addressable_shards[0].data.shape
        finally:
            d.stop()
        out[tp] = (toks, m)
    return out


# ---------------------------------------------------------------------------
# Byte-identity across mesh shapes
# ---------------------------------------------------------------------------


def test_greedy_byte_identity_across_meshes(greedy_by_tp):
    t1, m1 = greedy_by_tp[1]
    layers, n, d = m1["wq_t_shard"]
    for tp in (2, 4):
        toks, m = greedy_by_tp[tp]
        assert toks == t1, f"tp={tp} diverged from single-chip"
        # The held-transposed q [L, N, D] is column-parallel on N, the
        # axis the rule moved to (partition_rules: attn/w[qkv]_t).
        assert m["wq_t_shard"] == (layers, n // tp, d)


def test_prefix_sharing_and_cow_exercised_under_tp(greedy_by_tp):
    """The identity above must COVER the sharing machinery: the
    shared-prefix probes hit the trie on every mesh shape, map the full
    block by refcount, and CoW the partial tail — block bookkeeping is
    host-global and tp-invariant."""
    ref = None
    for tp, (_toks, m) in greedy_by_tp.items():
        assert m["prefix_hits"] >= 2, (tp, m["prefix_hits"])
        assert m["kv_shared_blocks"] >= 2
        assert m["kv_cow_copies"] >= 2
        counters = (m["prefix_hits"], m["kv_shared_blocks"],
                    m["kv_cow_copies"])
        assert ref is None or counters == ref
        ref = counters


def test_sampled_byte_identity_across_meshes(tiny_tp):
    """Temperature > 0: the RNG key is replicated and the categorical's
    noise is sharding-invariant, so sampled streams pin too."""
    outs = {}
    for tp in (1, 2):
        d = _decoder(tiny_tp, tp, seed=7)
        try:
            outs[tp] = _probe(d, temperature=0.8)
        finally:
            d.stop()
    assert outs[1] == outs[2]


def test_speculative_byte_identity_under_tp(tiny_tp):
    """Speculative verify rides the same sharded state: greedy tokens
    under tp=2 + speculation equal the plain single-chip stream."""
    plain = _decoder(tiny_tp, 1)
    try:
        ref = _probe(plain)
    finally:
        plain.stop()
    spec2 = _decoder(tiny_tp, 2, speculative_k=3)
    try:
        got = _probe(spec2)
        m = spec2.metrics()
    finally:
        spec2.stop()
    assert got == ref
    assert m["spec_verify_dispatches"] > 0  # speculation actually ran


def test_dense_layout_byte_identity_under_tp(tiny_tp):
    """tp also serves the dense layout (cache rows shard by KV head —
    no pool, no allocator)."""
    outs = {}
    for tp in (1, 2):
        d = _decoder(tiny_tp, tp, kv_layout="dense")
        try:
            outs[tp] = _probe(d)
        finally:
            d.stop()
    assert outs[1] == outs[2]


def test_int8_scales_ride_the_sharded_pool(tiny_tp):
    """Quantized codes AND abs-max scales shard by the same block ids:
    int8 tp=2 streams are byte-identical to int8 tp=1."""
    outs = {}
    for tp in (1, 2):
        d = _decoder(tiny_tp, tp, kv_dtype="int8")
        try:
            outs[tp] = _probe(d)
        finally:
            d.stop()
    assert outs[1] == outs[2]


def test_fused_mesh_twin_matches_gather_under_tp(tiny_tp):
    """kv_fused under tp routes the paged read through the kernel's
    shard_map twin; at f32 its tokens match the GSPMD gather path."""
    outs = {}
    for fused in (False, True):
        d = _decoder(tiny_tp, 2, kv_fused=fused)
        try:
            outs[fused] = _probe(d)
        finally:
            d.stop()
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# Op-level mesh twins (bitwise: per-head math is shard-local)
# ---------------------------------------------------------------------------


def _mk_pool(key, n, bs, hkv, hd, quant=False):
    vals = jax.random.normal(key, (n, bs, hkv, hd), jnp.float32)
    if not quant:
        return vals
    scale = jnp.max(jnp.abs(vals), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(vals / safe[..., None]), -127, 127)
    return {"q": q.astype(jnp.int8), "scale": scale}


@pytest.mark.parametrize("quant", [False, True])
def test_paged_decode_attention_mesh_twin_bitwise(quant):
    """The mesh twin's online-softmax state is per-head — no cross-
    shard reduction exists, so per-head outputs are BITWISE equal to
    the single-device walk (fp and quantized pools alike)."""
    mesh = serving_mesh(2)
    b, hkv, g, hd, n, bs, mb = 3, 4, 2, 16, 12, 8, 4
    key = jax.random.PRNGKey(3)
    kp = _mk_pool(key, n, bs, hkv, hd, quant)
    vp = _mk_pool(jax.random.fold_in(key, 1), n, bs, hkv, hd, quant)
    q = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv * g, hd))
    table = jnp.asarray(np.array([[0, 1, 2, 12], [3, 4, 12, 12],
                                  [5, 6, 7, 8]], np.int32))
    pos = jnp.asarray(np.array([17, 9, 25], np.int32))
    ref = paged_decode_attention(q, kp, vp, table, pos, n_kv_heads=hkv,
                                 implementation="xla")

    # The twin runs where the decoder runs it: inside jit (the legacy
    # shard_map shim's partial-auto mode is jit-only).
    @jax.jit
    def twin(q_, kp_, vp_, table_, pos_):
        return paged_decode_attention(q_, kp_, vp_, table_, pos_,
                                      n_kv_heads=hkv,
                                      implementation="xla", mesh=mesh,
                                      axis=AXIS_TENSOR)

    got = twin(q, kp, vp, table, pos)
    assert bool((np.asarray(got) == np.asarray(ref)).all())


def test_paged_span_attention_mesh_twin_bitwise():
    mesh = serving_mesh(4)
    b, s, hkv, g, hd, n, bs, mb = 2, 3, 4, 2, 16, 10, 8, 3
    key = jax.random.PRNGKey(5)
    kp = _mk_pool(key, n, bs, hkv, hd)
    vp = _mk_pool(jax.random.fold_in(key, 1), n, bs, hkv, hd)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (b, s, hkv * g, hd))
    table = jnp.asarray(np.array([[0, 1, 2], [3, 10, 10]], np.int32))
    pos = jnp.asarray(np.array([9, 4], np.int32))
    ref = paged_span_attention(q, kp, vp, table, pos, n_kv_heads=hkv)

    @jax.jit
    def twin(q_, kp_, vp_, table_, pos_):
        return paged_span_attention(q_, kp_, vp_, table_, pos_,
                                    n_kv_heads=hkv, mesh=mesh,
                                    axis=AXIS_TENSOR)

    got = twin(q, kp, vp, table, pos)
    assert bool((np.asarray(got) == np.asarray(ref)).all())


def test_mesh_twin_rejects_undivisible_heads():
    mesh = serving_mesh(4)
    q = jnp.zeros((1, 6, 8))
    kp = jnp.zeros((4, 8, 6, 8))
    table = jnp.zeros((1, 2), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="not divisible"):
        paged_decode_attention(q, kp, kp, table, pos, n_kv_heads=6,
                               mesh=mesh, axis=AXIS_TENSOR)


# ---------------------------------------------------------------------------
# Per-chip KV accounting
# ---------------------------------------------------------------------------


def test_kv_bytes_per_token_prices_per_shard():
    base = kv_bytes_per_token(2, 4, 16, 4)
    assert kv_bytes_per_token(2, 4, 16, 4, tp_shards=2) == base // 2
    assert kv_bytes_per_token(2, 4, 16, 4, tp_shards=4) == base // 4
    i8 = kv_bytes_per_token(2, 4, 16, 4, "int8")
    assert kv_bytes_per_token(2, 4, 16, 4, "int8", tp_shards=2) == i8 // 2
    with pytest.raises(ValueError, match="not divisible"):
        kv_bytes_per_token(2, 4, 16, 4, tp_shards=3)
    with pytest.raises(ValueError, match="tp_shards"):
        kv_bytes_per_token(2, 4, 16, 4, tp_shards=0)


def test_metrics_and_exposition_report_per_shard_bytes(tiny_tp):
    """The pool-fill signals the PR-8/9 autoscaler and gateway spill
    consume must reflect per-chip HBM: a tp=2 pool reports HALF the
    single-chip bytes per token (same block count, same fill ratio)."""
    ms = {}
    for tp in (1, 2):
        d = _decoder(tiny_tp, tp)
        try:
            ms[tp] = d.metrics()
            text = d.registry.render()
        finally:
            d.stop()
        assert f"serving_tp_shards {float(tp)}" in text \
            or f"serving_tp_shards {tp}" in text
    assert ms[1]["kv_blocks_total"] == ms[2]["kv_blocks_total"]
    assert ms[2]["kv_bytes_per_token"] * 2 == ms[1]["kv_bytes_per_token"]
    assert ms[2]["kv_bytes_total"] * 2 == ms[1]["kv_bytes_total"]
    assert ms[2]["tp_shards"] == 2


def test_tp_validation_errors(tiny_tp):
    spec, params = tiny_tp
    with pytest.raises(ValueError, match="n_kv_heads"):
        _decoder(tiny_tp, 3)
    with pytest.raises(ValueError, match="devices"):
        serving_mesh(len(jax.devices()) * 2)
    # kv heads divide (4 % 4 == 0) but query heads don't (6 % 4 != 0):
    # the head-split validation must fire before any tracing.
    bad = get_model("lm-test-tiny", n_kv_heads=4, n_heads=6,
                    dtype=jnp.float32)
    with pytest.raises(ValueError, match="n_heads"):
        ContinuousDecoder(bad.init(jax.random.PRNGKey(0), bad.config),
                          bad.config, slots=2, prefill_len=16,
                          max_new_tokens=8, tp_shards=4)


# ---------------------------------------------------------------------------
# Handoff across mesh shapes
# ---------------------------------------------------------------------------


def _handoff_decoder(tiny_tp, tp, **kw):
    kw.setdefault("prefix_cache_slots", 4)
    kw.setdefault("prefix_cache_min_len", 4)
    return _decoder(tiny_tp, tp, **kw)


@pytest.mark.parametrize("tp_export,tp_import", [(2, 1), (1, 2)])
def test_handoff_across_mesh_shapes(tiny_tp, tp_export, tp_import):
    """A sharded export is gathered host-side by the device fetch, so a
    differently-sharded importer scatters it with ITS pool sharding —
    decode after the handoff is byte-identical to colocated."""
    prompt = SHARED + [23, 29, 31]
    colo = _handoff_decoder(tiny_tp, tp_import)
    try:
        ref = colo.generate(prompt, 6, timeout=120)["tokens"]
    finally:
        colo.stop()
    exp = _handoff_decoder(tiny_tp, tp_export)
    imp = _handoff_decoder(tiny_tp, tp_import)
    try:
        handoff = exp.export_prompt(prompt)
        assert handoff["tp_shards"] == tp_export
        env = json.loads(json.dumps(handoff_mod.pack(handoff)))
        assert env["version"] == handoff_mod.HANDOFF_VERSION
        assert env["mesh"] == {"tpShards": tp_export, "cpShards": 1,
                               "ppStages": 1}
        unpacked = handoff_mod.unpack(env)
        assert unpacked["tp_shards"] == tp_export
        assert imp.import_prompt(unpacked)
        got = imp.generate(prompt, 6, timeout=120)["tokens"]
        m = imp.metrics()
    finally:
        exp.stop()
        imp.stop()
    assert got == ref
    assert m["kv_handoff_imports"] == 1
    assert m["prefix_hits"] >= 1  # the submit rode the imported prefix


def test_handoff_envelope_version_compat(tiny_tp):
    """Old (version-1, pre-mesh) envelopes still unpack — they are
    exactly tp=1 exports; unknown future versions are refused (the
    fleet path then degrades to a plain submit, never imports junk)."""
    d = _handoff_decoder(tiny_tp, 1)
    try:
        env = handoff_mod.pack(d.export_prompt(SHARED + [23, 29]))
    finally:
        d.stop()
    v1 = json.loads(json.dumps(env))
    v1.pop("mesh")
    v1["version"] = 1
    unpacked = handoff_mod.unpack(v1)
    assert unpacked["tp_shards"] == 1
    assert unpacked["tokens"] == env["tokens"]

    v3 = dict(env, version=3)
    with pytest.raises(ValueError, match="version"):
        handoff_mod.unpack(v3)
    with pytest.raises(ValueError, match="mesh"):
        handoff_mod.unpack(dict(env, mesh="nope"))


# ---------------------------------------------------------------------------
# Chaos: killing a sharded replica
# ---------------------------------------------------------------------------


def test_chaos_kill_sharded_replica_leaks_nothing(tiny_tp):
    """A tp=2 replica dies mid-stream inside a mixed fleet: its streams
    502 fast, the tp=1 survivor completes untouched, and the allocator
    leak check holds on every pool — block bookkeeping is host-side, so
    replica death under tp frees exactly like single-chip death."""
    from kubeflow_tpu.serving.fleet import (
        DecoderFleet,
        ReplicaUnavailableError,
    )

    reps = {"tp2": _decoder(tiny_tp, 2, max_new_tokens=64),
            "tp1": _decoder(tiny_tp, 1, max_new_tokens=64)}
    fleet = DecoderFleet(reps, affinity_tokens=4)
    try:
        home_of = {}
        probe = 0
        while set(home_of) != set(reps) and probe < 300:
            toks = [3 + probe % 11, 5, 7, probe % 13 + 2]
            home_of.setdefault(fleet.route(toks), toks)
            probe += 1
        assert set(home_of) == set(reps)

        handles = {nm: fleet.submit(toks, 60)
                   for nm, toks in home_of.items()}
        stream = handles["tp2"].tokens(timeout=60)
        next(stream)  # live mid-stream
        with reps["tp2"]._state_lock:
            reps["tp2"]._state = None
        t0 = time.perf_counter()
        with pytest.raises(ReplicaUnavailableError) as err:
            for _ in stream:
                pass
        assert err.value.code == 502
        assert time.perf_counter() - t0 < 10
        assert fleet.live_members() == ["tp1"]

        assert len(handles["tp1"].result(timeout=60)["tokens"]) == 60
        # Dead replica's keys remap onto the survivor.
        h2 = fleet.submit(home_of["tp2"], 4)
        assert h2.replica == "tp1"
        h2.result(timeout=60)
        # Zero slot-held blocks anywhere — including the dead sharded
        # replica, whose crash sweep freed its reservations.
        for nm, d in reps.items():
            assert all(not b for b in d._slot_blocks), nm
        assert fleet.metrics()["kv_blocks_in_use"] == 0
    finally:
        fleet.stop()


# ---------------------------------------------------------------------------
# Control-plane plumbing
# ---------------------------------------------------------------------------


def test_tpu_serving_prototype_renders_tp_flag():
    from kubeflow_tpu.manifests.core import generate

    objs = generate("tpu-serving", {"name": "m", "tp_shards": 2})
    dep = next(o for o in objs if o["kind"] == "Deployment")
    args = dep["spec"]["template"]["spec"]["containers"][0]["args"]
    assert "--tp-shards=2" in args


def test_operator_normalizes_tp_shards_and_sizes_chips():
    from kubeflow_tpu.operators.inference import (
        InferenceServiceController,
    )

    spec = {"replicas": 1,
            "engine": {"tpShards": 4, "kv_layout": "paged"},
            "roles": {"decode": {"engine": {"tpShards": 2}},
                      "prefill": {}}}
    decode = InferenceServiceController._pool_spec(spec, "decode")
    assert decode["engine"]["tp_shards"] == 2  # role override wins
    assert decode["engine"]["serving_role"] == "decode"
    prefill = InferenceServiceController._pool_spec(spec, "prefill")
    assert prefill["engine"]["tp_shards"] == 4  # inherits top level

    ctl = InferenceServiceController.__new__(InferenceServiceController)
    svc = {"apiVersion": "kubeflow-tpu.org/v1",
           "kind": "InferenceService",
           "metadata": {"name": "m", "namespace": "kubeflow"},
           "spec": {"model": "m",
                    "engine": {"tpShards": 2, "kv_layout": "paged"}}}
    objs = ctl._replica_objects(svc, 0)
    dep = next(o for o in objs if o["kind"] == "Deployment")
    c = dep["spec"]["template"]["spec"]["containers"][0]
    assert "--tp-shards=2" in c["args"]
    # tpShards sizes the chip request when the spec doesn't pin it.
    assert c["resources"]["limits"]["google.com/tpu"] == "2" \
        or c["resources"]["limits"]["google.com/tpu"] == 2
    # An explicit tpuChipsPerReplica wins (0 = CPU stays CPU).
    svc["spec"]["tpuChipsPerReplica"] = 0
    objs = ctl._replica_objects(svc, 0)
    dep = next(o for o in objs if o["kind"] == "Deployment")
    assert "resources" not in dep["spec"]["template"]["spec"][
        "containers"][0] or not dep["spec"]["template"]["spec"][
        "containers"][0].get("resources", {}).get("limits", {}).get(
        "google.com/tpu")


def test_engine_config_and_cli_flag():
    from kubeflow_tpu.serving.engine import EngineConfig
    from kubeflow_tpu.serving.__main__ import main as cli_main

    assert EngineConfig().tp_shards == 1
    with pytest.raises(SystemExit):
        cli_main(["--model-name", "lm-test-tiny", "--tp-shards", "0"])
    with pytest.raises(SystemExit):
        cli_main(["--model-name", "lm-test-tiny", "--tp-shards", "2",
                  "--decode-mode", "lockstep"])
