"""Normalization ops.

RMSNorm ships both as a fused pallas kernel (one HBM round-trip: read x,
write y — mean-of-squares, rsqrt, and the weight multiply all happen in VMEM)
and as pure jax. LayerNorm is pure jax; XLA's fusion handles it well and it
only appears in the BERT family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.utils.jaxenv import not_tpu


def rms_norm(x, weight, *, eps: float = 1e-6, implementation: str | None = None):
    """y = x / rms(x) * weight over the last dim. x: [..., D], weight: [D].

    Auto is the pure-XLA path: measured inside the full flagship train step
    on v5e, XLA's fused norm edges out the pallas kernel (27.5k vs 27.0k
    tok/s end-to-end) — XLA already fuses the norm into its neighbors, and
    the kernel boundary blocks that. The kernel stays opt-in
    (``implementation="pallas"``, TPU only — elsewhere it raises) for
    standalone-norm workloads."""
    if implementation == "pallas":
        if why := not_tpu():
            # The kernel is compiled, never quietly interpreted: an
            # interpreted run would pass for a kernel run in a benchmark.
            raise ValueError(
                f"rms_norm(implementation='pallas') compiles a TPU kernel: "
                f"{why}")
        return _rms_norm_fused(x, weight, eps)
    return _rms_norm_xla(x, weight, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_fused(x, weight, eps):
    # Autodiff must not see the pallas_call (no reverse-mode rule); the
    # backward is the closed-form VJP below.
    return _rms_norm_pallas(x, weight, eps=eps, interpret=False)


def _rms_norm_fused_fwd(x, weight, eps):
    return _rms_norm_fused(x, weight, eps), (x, weight)


def _rms_norm_fused_bwd(eps, res, g):
    x, weight = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    gw = g32 * w32
    # d/dx [x·r(x)·w]: r·gw − r³·x·mean(gw·x)
    dx = r * gw - (r**3) * x32 * jnp.mean(gw * x32, axis=-1, keepdims=True)
    dw = jnp.sum(g32 * x32 * r, axis=tuple(range(x32.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(weight.dtype)


_rms_norm_fused.defvjp(_rms_norm_fused_fwd, _rms_norm_fused_bwd)


def _rms_norm_xla(x, weight, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _rms_norm_pallas(x, weight, *, eps, interpret):
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    # Keep the f32 working set well under the 16M scoped-vmem limit: in/out
    # blocks + float32 intermediates ≈ 12·rows·d bytes.
    block_rows = max(8, min(rows, 524_288 // d))
    if rows % block_rows:
        block_rows = rows
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, weight)
    return out.reshape(orig_shape)


def layer_norm(x, weight, bias, *, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(
        x.dtype
    )
