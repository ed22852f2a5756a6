"""The routed experts' grouped MLP as one kernel
(``ops/grouped_experts.py:grouped_expert_mlp``), in interpret mode, against
what ``transformer.moe_dropless`` runs where the kernel does not: the
three ragged-dot products of ``transformer._grouped`` and the SiLU gate.
Each case runs the expert layer through the kernel (the program's own
routing, sort and un-sort around it) and compares the kernel's products
with XLA's on the same sorted pairs, and the layer's output with the
layer's on XLA's path. Then what chooses the kernel, and what the decoder
says it chose."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import transformer
from kubeflow_tpu.ops import grouped_experts

# Lane-aligned widths, 8 experts, 2 a token; three expert layers.
CFG = transformer.config("kanana-test-tiny", d_model=128, d_expert=128,
                         dtype=jnp.bfloat16)
LAYERS = 3


def _mlp(key, cfg):
    """One expert layer's tree with the experts as WHOLE stacks
    [LAYERS, E, ...] (the decode step's way), bf16."""
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    ks = jax.random.split(key, 6)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in ** -0.5).astype(
            jnp.bfloat16)

    return {"router": jax.random.normal(ks[0], (d, e)) * d ** -0.5,
            "bias": jnp.zeros((e,), jnp.float32),
            "gate": w(ks[1], (LAYERS, e, d, f), d),
            "up": w(ks[2], (LAYERS, e, d, f), d),
            "down": w(ks[3], (LAYERS, e, f, d), f),
            "shared_gate": w(ks[4], (d, f), d),
            "shared_up": w(ks[5], (d, f), d),
            "shared_down": w(ks[4], (f, d), f)}


# name: (tokens, choices a token, experts the bias pushes every token to,
# pad tokens, whether the stacks' other layers and the layer's untouched
# experts hold NaN).
CASES = {
    "an-expert-with-no-rows": (3, 2, (), (), False),
    "a-group-larger-than-the-tile": (24, 2, (3,), (), False),
    "every-pair-on-one-expert": (20, 1, (5,), (), False),
    "pad-tokens": (9, 2, (), (1, 4, 8), False),
    "pairs-not-whole-tiles": (13, 2, (), (), False),
    "the-layer-of-a-nan-stack": (11, 2, (), (), True),
}
LAYER = 1


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_computes_the_grouped_products(case, monkeypatch):
    tokens, k, pushed, pads, nan = CASES[case]
    cfg = dataclasses.replace(CFG, expert_top_k=k)
    mlp = _mlp(jax.random.PRNGKey(7), cfg)
    for e in pushed:
        mlp["bias"] = mlp["bias"].at[e].set(100.0)
    u = jax.random.normal(jax.random.PRNGKey(8), (tokens, cfg.d_model)
                          ).astype(jnp.bfloat16)
    valid = (None if not pads else
             jnp.asarray([i not in pads for i in range(tokens)]))
    clean = dict(mlp)
    if nan:
        # What the layer routes to, from the XLA path on clean stacks: every
        # other matrix of the stacks is NaN, so a block read anywhere else
        # shows in the output.
        _, routed = transformer.moe_dropless(u, mlp, cfg, valid, LAYER)
        keep = (jnp.arange(LAYERS)[:, None] == LAYER) & (routed > 0)[None]
        for name in ("gate", "up", "down"):
            mlp[name] = jnp.where(keep[:, :, None, None], mlp[name], jnp.nan)

    calls = []

    def interpreted(xs, sizes, layer, *stacks):
        out = grouped_experts.grouped_expert_mlp(xs, sizes, layer, *stacks,
                                                 interpret=True)
        calls.append((xs, sizes, layer, out))
        return out

    monkeypatch.setattr(transformer, "grouped_experts_implementation",
                        lambda *a: "pallas")
    monkeypatch.setattr(transformer, "grouped_expert_mlp", interpreted)
    y, routed = transformer.moe_dropless(u, mlp, cfg, valid, LAYER)
    monkeypatch.undo()
    want_y, want_routed = transformer.moe_dropless(u, clean, cfg, valid,
                                                   LAYER)

    (xs, sizes, layer, got), = calls
    sizes = np.asarray(sizes)
    pairs = tokens * k
    # Each case's premise.
    if case == "an-expert-with-no-rows":
        assert (sizes == 0).any()
    if case == "a-group-larger-than-the-tile":
        assert sizes.max() > grouped_experts.ROW_TILE
    if case == "every-pair-on-one-expert":
        assert sorted(sizes.tolist())[-1] == pairs and (sizes > 0).sum() == 1
    if case == "pad-tokens":
        assert int(routed.sum()) == (tokens - len(pads)) * k
    if case == "pairs-not-whole-tiles":
        assert pairs % grouped_experts.ROW_TILE
    assert int(layer) == LAYER and got.shape == (pairs, cfg.d_model)

    # XLA's three products on the same sorted pairs, from the layer's slice.
    layer_of = {name: clean[name][LAYER] for name in ("gate", "up", "down")}
    sizes = jnp.asarray(sizes)
    g = transformer._grouped(xs, layer_of["gate"], sizes, None)
    up = transformer._grouped(xs, layer_of["up"], sizes, None)
    want = transformer._grouped((jax.nn.silu(g) * up).astype(jnp.bfloat16),
                                layer_of["down"], sizes, None)
    got, want = np.asarray(got), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=2 ** -6 * scale)
    # ... and no further from the exact float32 products than XLA's are.
    with jax.default_matmul_precision("highest"):
        exact = np.concatenate([np.asarray(
            (jax.nn.silu(x @ layer_of["gate"][e].astype(jnp.float32))
             * (x @ layer_of["up"][e].astype(jnp.float32)))
            @ layer_of["down"][e].astype(jnp.float32))
            for e, x in enumerate(np.split(
                np.asarray(xs, np.float32), np.cumsum(sizes)[:-1]))])
    assert np.abs(got - exact).max() <= np.abs(want - exact).max()

    assert routed.tolist() == want_routed.tolist()
    y, want_y = np.asarray(y, np.float32), np.asarray(want_y, np.float32)
    np.testing.assert_allclose(y, want_y, rtol=0,
                               atol=2 ** -5 * np.abs(want_y).max())


# Kanana-2-30B-A3B's decode step: 64 rows, 6 of 128 experts a token.
KANANA = (64 * 6, 128, 2048, 768)


def test_off_the_tpu_the_xla_products_stay():
    assert grouped_experts.grouped_experts_implementation(
        *KANANA, jnp.bfloat16) == "xla"


def test_on_a_tpu_the_choice_goes_by_dtype_mesh_and_pairs(monkeypatch):
    from kubeflow_tpu.parallel.mesh import serving_mesh

    monkeypatch.setattr(grouped_experts, "not_tpu", lambda: None)
    choose = grouped_experts.grouped_experts_implementation
    assert choose(*KANANA, jnp.bfloat16) == "pallas"
    assert choose(*KANANA, jnp.float32) == "xla"
    assert choose(*KANANA, jnp.bfloat16, serving_mesh(2)) == "xla"
    assert choose(16 * 128, 128, 2048, 768, jnp.bfloat16) == "pallas"
    # Past 16 pairs an expert: an admission's [1, 2048] prefill.
    assert choose(16 * 128 + 1, 128, 2048, 768, jnp.bfloat16) == "xla"
    assert choose(2048 * 6, 128, 2048, 768, jnp.bfloat16) == "xla"
    assert choose(384, 8, 64, 32, jnp.bfloat16) == "xla"  # the tiny widths


@pytest.mark.parametrize("preset,want", [("kanana-test-tiny", "xla"),
                                         ("lm-test-tiny", "")])
def test_the_decoder_says_what_its_experts_run_on(preset, want):
    from kubeflow_tpu.serving.continuous import ContinuousDecoder

    cfg = transformer.config(preset)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    dec = ContinuousDecoder(params, cfg, slots=2, prefill_len=8,
                            max_new_tokens=4)
    try:
        assert dec.metrics()["moe_experts_impl"] == want
    finally:
        dec.stop()
