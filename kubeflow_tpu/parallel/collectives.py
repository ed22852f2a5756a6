"""XLA collective wrappers for shard_map code.

Replaces the reference's three transport stacks — TF gRPC parameter servers
(tf-controller-examples/tf-cnn/launcher.py:69-81), OpenMPI ORTE
(kubeflow/mpi-job/mpi-operator.libsonnet:280), and NCCL inside imported GPU
images — with the XLA collectives that ride ICI within a slice and DCN across
slices. These helpers are thin by design: under ``jit`` + sharding constraints
XLA usually inserts collectives itself; explicit calls are for shard_map
regions (ring attention, custom allreduce benchmarks, MoE dispatch).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma: bool = False,
              axis_names=None):
    """``jax.shard_map`` with this repo's default: ``check_vma=False``,
    because collective-heavy kernels (and pallas calls) routinely mix
    replicated and sharded values. ``axis_names`` limits the manual axes;
    None makes every mesh axis manual."""
    kwargs = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


def psum(x, axis: str | Sequence[str]):
    return lax.psum(x, axis_name=axis)


def pmean(x, axis: str | Sequence[str]):
    return lax.pmean(x, axis_name=axis)


def all_gather(x, axis: str, *, dim: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name=axis, axis=dim, tiled=tiled)


def reduce_scatter(x, axis: str, *, dim: int = 0):
    return lax.psum_scatter(x, axis_name=axis, scatter_dimension=dim, tiled=True)


def ring_permute(x, axis: str, *, shift: int = 1):
    """Send x to the neighbor ``shift`` steps around the ring; receive from
    the opposite neighbor. The building block of ring attention and of
    bidirectional-bandwidth allreduce on a torus."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Static mesh-axis size inside a shard_map region."""
    return lax.axis_size(axis)


def shard_map_over(mesh: Mesh, in_specs, out_specs, *, check_vma: bool = False):
    """Decorator: shard_map a function over ``mesh``.

    ``check_vma=False`` by default because collective-heavy kernels routinely
    mix replicated and sharded values.
    """

    def wrap(fn):
        return shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
        )

    return wrap


def allreduce_mean(mesh: Mesh, axis: str):
    """An explicit-allreduce jitted fn (psum / n, the Horovod convention) —
    the MPIJob benchmark analogue
    (kubeflow/mpi-job/prototypes/mpi-job-custom.jsonnet:35-59), for measuring
    collective bandwidth over ICI rather than for training (training uses
    jit+GSPMD)."""

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    def _allreduce(x):
        return lax.pmean(x, axis_name=axis)

    return _allreduce


def global_norm_sq(tree, axis: str | Sequence[str] | None = None):
    """Sum of squares across a pytree, optionally psummed across ``axis``
    (for use inside shard_map gradient code)."""
    leaves = jax.tree.leaves(tree)
    total = sum(jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in leaves)
    if axis is not None:
        total = lax.psum(total, axis_name=axis)
    return total
