"""The routed experts' MLP of one expert layer as one Mosaic call: every
(token, choice) pair through its expert's gate, up, SiLU and down, each
touched expert's three matrices streamed once out of the whole stacks.

``transformer.moe_dropless`` sorts the pairs by expert and runs each
expert's group through its matrices. XLA's grouped product
(``lax.ragged_dot``) does that as three calls a layer, gate, up and down,
each over every group of the stack with a tile of 128 rows a group and
weight blocks of 512 × 256: at a decode step's groups of about three rows
an expert it streams a touched expert at about half the chip's bandwidth.
The kernel below visits only the experts the layer touched, each once, in
blocks of whole matrices, and fuses the three products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from kubeflow_tpu.utils.jaxenv import not_tpu

# Rows of one tile: a tile is ``ROW_TILE`` consecutive sorted pairs, and an
# expert whose group crosses a tile's edge is visited once a tile its group
# touches (the weight blocks stay where they are between such visits, so
# no expert is copied twice). 16 is the least tile of a bf16 operand; 32
# timed the same at moe-decode's shapes on a v5e (PERF.md §6). The kernel
# engages where the pairs are at most one tile an expert on average
# (``ROW_TILE × n_experts``): a decode step's 384 pairs over 128 experts.
# Past it a group spans several tiles, each a pass of the MXU over the
# expert's matrices for 16 rows, where ragged-dot's 128-row tiles do the
# same rows in fewer passes (an admission's 12,288 pairs, 96 an expert).
ROW_TILE = 16


def _kernel_unsupported(pairs: int, n_experts: int, d_model: int,
                        d_expert: int, dtype, mesh=None) -> str | None:
    """Why the kernel does not run these products (None = it does)."""
    if why := not_tpu():
        return why
    if mesh is not None:
        return "under a mesh (GSPMD cannot partition a Mosaic call)"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return f"the experts are {jnp.dtype(dtype).name}, not bfloat16"
    if d_model % 128 or d_expert % 128:
        return (f"widths {d_model} and {d_expert} must be multiples of 128")
    if pairs > ROW_TILE * n_experts:
        return (f"{pairs} pairs over {n_experts} experts pass "
                f"{ROW_TILE} an expert")
    return None


def grouped_experts_implementation(pairs: int, n_experts: int, d_model: int,
                                   d_expert: int, dtype, mesh=None) -> str:
    """What runs an expert layer's grouped products for ``pairs`` sorted
    (token, choice) pairs: ``"pallas"`` (:func:`grouped_expert_mlp`) on a
    TPU for bf16 experts of lane-aligned widths, no mesh, and at most
    ``ROW_TILE`` pairs an expert; else ``"xla"`` (three
    ``lax.ragged_dot`` calls)."""
    return ("xla" if _kernel_unsupported(pairs, n_experts, d_model, d_expert,
                                         dtype, mesh) else "pallas")


def _visits(sizes, pairs: int):
    """The kernel's grid: one visit for each (touched expert, tile its
    group touches), in order of expert, then tile. Returns ``(expert,
    tile, first row, end row)`` of every visit and the number of visits;
    the grid's steps past that repeat the last visit."""
    n_tiles = -(-pairs // ROW_TILE)
    steps = n_tiles + min(sizes.shape[0], pairs) - 1  # the most visits
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // ROW_TILE
    span = jnp.where(sizes > 0, (ends - 1) // ROW_TILE - first + 1, 0)
    visit_end = jnp.cumsum(span)
    count = visit_end[-1]
    step = jnp.minimum(jnp.arange(steps), count - 1)
    expert = jnp.sum(visit_end[None, :] <= step[:, None], axis=1,
                     dtype=jnp.int32)
    tile = first[expert] + step - (visit_end - span)[expert]
    return (expert.astype(jnp.int32), tile.astype(jnp.int32),
            starts[expert].astype(jnp.int32), ends[expert].astype(jnp.int32),
            count.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_expert_mlp(xs, sizes, layer, gate, up, down, *,
                       interpret: bool = False):
    """``down_e(silu(x gate_e) * (x up_e))`` of every sorted pair ``x``
    with its expert ``e``: xs [P, D] (pairs sorted by expert), sizes [E]
    (the rows of each expert, summing to P), layer (traced) the expert
    layer of the WHOLE stacks gate/up [L, E, D, F] and down [L, E, F, D].
    Returns [P, D] float32. Jitted on its own, so that a process traces
    the kernel once a shape however many programs hold it.

    Grid: one step a visit (:func:`_visits`), in order. A step's blocks are
    the expert's three matrices whole, ``(layer, expert)`` of each stack
    where it lies (no slice of a stack is made), and the tile's
    ``ROW_TILE`` pairs; the pipeline copies the next visit's blocks while
    this one computes, and copies nothing where a block's index repeats (an
    expert visited for a second tile, the steps past the last visit). A
    tile's rows of other experts are computed and masked out; each row of
    the output is written by its own expert's visit, in the tile's output
    block, which stays in VMEM while its visits run.

    Operands enter the MXU at bf16 and accumulate in float32; ``silu(g) *
    u`` is rounded to bf16 before ``down``, as the XLA path rounds it (that
    path also rounds ``g`` and ``u``, and its output, to bf16)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pairs, d = xs.shape
    f = gate.shape[-1]
    tiles = -(-pairs // ROW_TILE)
    if tiles * ROW_TILE != pairs:
        xs = jnp.pad(xs, ((0, tiles * ROW_TILE - pairs), (0, 0)))
    expert, tile, lo, hi, count = _visits(sizes, pairs)

    def kernel(layer_ref, expert_ref, tile_ref, lo_ref, hi_ref, count_ref,
               x_ref, gate_ref, up_ref, down_ref, o_ref):
        i = pl.program_id(0)

        @pl.when(i < count_ref[0])
        def _visit():
            x = x_ref[...]
            g = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
            u = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * u).astype(x.dtype)
            y = jnp.dot(h, down_ref[...], preferred_element_type=jnp.float32)
            row = (tile_ref[i] * ROW_TILE
                   + lax.broadcasted_iota(jnp.int32, (ROW_TILE, 1), 0))
            own = (row >= lo_ref[i]) & (row < hi_ref[i])
            o_ref[...] = jnp.where(own, y, o_ref[...])

    def weights(*block):
        return pl.BlockSpec((None, None, *block),
                            lambda i, layer, expert, *_: (layer[0], expert[i],
                                                          0, 0))

    rows = pl.BlockSpec((ROW_TILE, d), lambda i, layer, expert, tile, *_:
                        (tile[i], 0))
    block_bytes = 3 * d * f * gate.dtype.itemsize
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((tiles * ROW_TILE, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(expert.shape[0],),
            in_specs=[rows, weights(d, f), weights(d, f), weights(f, d)],
            out_specs=rows),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # Two visits' matrices (the pipeline's two buffers) and room.
            vmem_limit_bytes=2 * block_bytes + (16 << 20)),
        interpret=interpret,
        name="grouped_expert_mlp",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), expert, tile, lo, hi,
      jnp.reshape(count, (1,)), xs, gate, up, down)
    return out[:pairs]
