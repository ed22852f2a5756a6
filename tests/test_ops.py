"""Kernel numerics tests against dense references (pallas paths run in
interpreter mode on the CPU fake slice)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops import (
    apply_rotary,
    flash_attention,
    layer_norm,
    rms_norm,
    rotary_frequencies,
    softmax_cross_entropy,
)
from kubeflow_tpu.ops.norms import _rms_norm_pallas


def dense_attention(q, k, v, causal):
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if hkv != h:
        reps = h // hkv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) / (d**0.5)
    if causal:
        mask = np.tril(np.ones((t, k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("impl", ["xla", "plain"])
def test_flash_attention_forward(causal, impl):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (2, 128, 4, 32))
    k = jax.random.normal(kk, (2, 128, 4, 32))
    v = jax.random.normal(kv, (2, 128, 4, 32))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          implementation=impl)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_gqa():
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (2, 64, 8, 16))   # 8 query heads
    k = jax.random.normal(kk, (2, 64, 2, 16))   # 2 kv heads
    v = jax.random.normal(kv, (2, 64, 2, 16))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          implementation="xla")
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grad_matches_dense():
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (1, 64, 2, 16)
    q = jax.random.normal(kq, shape)
    k = jax.random.normal(kk, shape)
    v = jax.random.normal(kv, shape)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            implementation="xla") ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4)


def test_rms_norm_pallas_matches_xla():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 64, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(4), (256,)) * 0.1 + 1.0
    ref = rms_norm(x, w, implementation=None)  # xla on cpu
    out = _rms_norm_pallas(x, w, eps=1e-6, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_layer_norm_matches_numpy():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 32))
    w = jnp.ones((32,)) * 1.5
    b = jnp.ones((32,)) * 0.25
    out = np.asarray(layer_norm(x, w, b))
    xn = np.asarray(x, np.float32)
    ref = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
        xn.var(-1, keepdims=True) + 1e-6
    ) * 1.5 + 0.25
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_rotary_preserves_norm_and_is_position_dependent():
    cos, sin = rotary_frequencies(16, 128)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, 2, 16))
    y = apply_rotary(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        atol=1e-4,
    )
    # Position 0 is identity rotation.
    np.testing.assert_allclose(
        np.asarray(y[:, 0]), np.asarray(x[:, 0]), atol=1e-5
    )
    assert not np.allclose(np.asarray(y[:, 1]), np.asarray(x[:, 1]))


def test_rotary_with_explicit_positions_matches_default():
    cos, sin = rotary_frequencies(8, 64)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 1, 8))
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    np.testing.assert_allclose(
        np.asarray(apply_rotary(x, cos, sin, positions=pos)),
        np.asarray(apply_rotary(x, cos, sin)),
        atol=1e-6,
    )


def test_cross_entropy_matches_dense_and_masks():
    logits = jax.random.normal(jax.random.PRNGKey(8), (4, 8, 32))
    labels = jax.random.randint(jax.random.PRNGKey(9), (4, 8), 0, 32)
    labels = labels.at[0, 0].set(-1)  # ignored position
    loss, metrics = softmax_cross_entropy(logits, labels)
    # Dense reference.
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    mask = np.asarray(labels >= 0)
    ref = float(np.asarray(nll)[mask].mean())
    np.testing.assert_allclose(float(loss), ref, rtol=1e-6)
    assert float(metrics["tokens"]) == mask.sum()


def test_cross_entropy_gradient_is_softmax_minus_onehot():
    # Regression: the subtracted max must be stop-gradiented consistently,
    # else the argmax logit gains a spurious +1 gradient.
    logits = jnp.array([[[2.0, 1.0, 0.5]]])
    labels = jnp.array([[2]])

    def loss(lg):
        return softmax_cross_entropy(lg, labels)[0]

    g = np.asarray(jax.grad(loss)(logits))[0, 0]
    p = np.asarray(jax.nn.softmax(logits[0, 0]))
    expected = p - np.array([0.0, 0.0, 1.0])
    np.testing.assert_allclose(g, expected, atol=1e-6)


def test_cross_entropy_z_loss_positive():
    logits = jax.random.normal(jax.random.PRNGKey(10), (2, 4, 16)) * 5
    labels = jnp.zeros((2, 4), jnp.int32)
    loss_plain, _ = softmax_cross_entropy(logits, labels)
    loss_z, metrics = softmax_cross_entropy(logits, labels, z_loss=1e-2)
    assert float(loss_z) > float(loss_plain)
    assert float(metrics["z_loss"]) > 0


@pytest.mark.parametrize("impl", ["xla", "plain"])
def test_flash_attention_all_masked_rows_are_zero(impl):
    # A batch element whose kv_mask is all-zero must return zeros (not the
    # mean of V) and contribute zero gradient.
    key = jax.random.PRNGKey(3)
    q, k, v = (
        jax.random.normal(kk, (2, 16, 2, 32), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    kv_mask = jnp.stack([jnp.zeros((16,)), jnp.ones((16,))])
    out = flash_attention(q, k, v, causal=False, kv_mask=kv_mask,
                          implementation=impl)
    np.testing.assert_allclose(np.asarray(out[0]), 0.0, atol=1e-6)
    assert np.abs(np.asarray(out[1])).max() > 0

    def loss(v):
        return jnp.sum(
            flash_attention(q, k, v, causal=False, kv_mask=kv_mask,
                            implementation=impl) ** 2
        )

    dv = jax.grad(loss)(v)
    np.testing.assert_allclose(np.asarray(dv[0]), 0.0, atol=1e-6)


@pytest.mark.parametrize("impl", ["pallas", "splash"])
def test_explicit_tpu_kernel_request_raises_off_tpu(impl):
    """An EXPLICIT kernel request that cannot be honoured raises — it
    never quietly runs the XLA scan under the kernel's name. Auto
    (implementation=None) is what routes to the XLA path off-TPU, so one
    model definition still runs everywhere; on-TPU parity of the kernels
    with the XLA path is chip_smoke.py's kernels phase."""
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (2, 512, 8, 128))
    k = jax.random.normal(kk, (2, 512, 2, 128))
    v = jax.random.normal(kv, (2, 512, 2, 128))
    with pytest.raises(ValueError, match="backend is 'cpu'"):
        flash_attention(q, k, v, causal=True, implementation=impl)
    # Same kernel-eligible shape, auto: the XLA path, and it is right.
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_splash_kernel_cache_holds_no_tracers():
    """The cached splash kernel object outlives the trace that first
    builds it; its mask tables must be concrete arrays, or the next
    trace is handed tracers of a finished one (seen on the chip as
    UnexpectedTracerError on the second mesh a process trained on)."""
    from kubeflow_tpu.ops.attention import _splash_kernel

    _splash_kernel.cache_clear()

    def traced(x):
        kernel = _splash_kernel(2, 256, 256, True, 128)
        leaves = jax.tree.leaves(kernel)
        assert leaves and not any(
            isinstance(leaf, jax.core.Tracer) for leaf in leaves)
        return x

    jax.jit(traced)(1.0)
    _splash_kernel.cache_clear()


def test_explicit_paged_and_norm_kernels_raise_off_tpu():
    """Same rule for the other two kernels: compiled on request or an
    error, interpreted only when the caller says interpret=True."""
    from kubeflow_tpu.ops.attention import paged_decode_attention

    q = jnp.ones((2, 4, 128))
    pool = jnp.ones((4, 8, 2, 128))
    table = jnp.zeros((2, 2), jnp.int32)
    pos = jnp.array([3, 9], jnp.int32)
    with pytest.raises(ValueError, match="backend is 'cpu'"):
        paged_decode_attention(q, pool, pool, table, pos, n_kv_heads=2,
                               implementation="pallas")
    out = paged_decode_attention(q, pool, pool, table, pos, n_kv_heads=2,
                                 implementation="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-6)
    with pytest.raises(ValueError, match="backend is 'cpu'"):
        rms_norm(jnp.ones((8, 128)), jnp.ones((128,)),
                 implementation="pallas")


def test_tpu_kernels_are_fenced_off_inside_a_shard_map(monkeypatch):
    """A pipeline stage is already a ``shard_map`` over `pipeline`; the
    kernels would need a second, nested wrap there that has never run on
    a chip. So the predicate says no inside one: auto takes the XLA path
    and an explicit request raises (checked with the backend test out of
    the way — on the CPU it would answer first)."""
    from jax.sharding import PartitionSpec as P

    from kubeflow_tpu.ops import attention
    from kubeflow_tpu.parallel.collectives import shard_map
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh

    monkeypatch.setattr(attention, "not_tpu", lambda: None)
    mesh = build_mesh(MeshConfig(data=1, pipeline=2, tensor=2),
                      devices=jax.devices()[:4])
    q = jnp.ones((2, 512, 8, 128))
    kv = jnp.ones((2, 512, 2, 128))
    assert attention._kernel_unsupported(q, kv, None, mesh) is None
    seen = []

    def stage(x):
        seen.append(attention._kernel_unsupported(q, kv, None))
        with pytest.raises(ValueError, match="inside a shard_map"):
            flash_attention(q, kv, kv, implementation="splash")
        return x

    jax.jit(shard_map(stage, mesh=mesh, in_specs=P("pipeline"),
                      out_specs=P("pipeline"),
                      axis_names=frozenset({"pipeline"})))(jnp.ones((2,)))
    assert seen and "inside a shard_map" in seen[0]
