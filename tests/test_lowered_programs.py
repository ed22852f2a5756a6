"""The programs the existing presets compile, held to their lowered text.

Adding a model kind to the shared decode path (models/decode.py's layer
loop, the decode step, the admission, the FFN's dispatch) must leave what
every other preset traces as it was. Each case lowers one jitted program
of a tiny preset on the CPU and compares the sha256 of its StableHLO text
(without locations, so a moved line changes nothing) with the hash the
program had before latent attention and the dropless expert layer came
in. A case that fails has changed the program that preset runs: find the
statement, or, where the change is meant, say so and write the new hash.

    JAX_PLATFORMS=cpu python -m pytest tests/test_lowered_programs.py -q
    JAX_PLATFORMS=cpu python tests/test_lowered_programs.py   # print hashes
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models import decode, transformer

# sha256 of ``lowered.as_text()``, first 16 hex digits (jax 0.9.0, CPU).
EXPECTED = {
    "lm-test-tiny.decode_step.dense": "11fa8e33dae44f0e",
    "lm-test-tiny.decode_step.paged": "64948a0defa3c03e",
    "lm-test-tiny.decode_chunk.dense": "879c198f80db5e85",
    "lm-test-tiny.admit_rows_and_step.dense": "6ba42629c22f13dc",
    "lm-test-tiny.paged_prefill_chunk": "a18b82b80e1165e0",
    "sala-test-tiny.decode_step.paged": "e62c77040eba50d4",
    "ouro-test-tiny.decode_step.dense": "0e9e95ef7e9615c7",
    "moe-test-tiny.apply": "eecf1f4ca7e85b23",
    # The latent model's programs as they were before its decode step ran
    # the routed experts through a kernel on the TPU: off the TPU the
    # expert layer is the three grouped products, as it was.
    "kanana-test-tiny.decode_step.dense": "f670ebcd4e107ef1",
    "kanana-test-tiny.decode_chunk.dense": "5224a6a29cb14321",
    "kanana-test-tiny.admit_rows_and_step.dense": "014613f0bc5909f4",
    "kanana-test-tiny.apply": "93afef88b556ed82",
    "lm-test-tiny.train_grad": "bcee49537f0b9081",
}

SLOTS, TOTAL, BLOCK = 4, 64, 8


def _params(cfg):
    return jax.eval_shape(lambda: transformer.serving_params(
        transformer.init(jax.random.PRNGKey(0), cfg), cfg))


def _shapes(tree):
    return jax.eval_shape(lambda: tree)


def _lower(name: str):
    preset = name.split(".")[0]
    cfg = transformer.config(preset)
    i32 = jnp.int32
    if name.endswith("train_grad"):
        params = jax.eval_shape(
            lambda: transformer.init(jax.random.PRNGKey(0), cfg))
        batch = {"tokens": jax.ShapeDtypeStruct((2, 33), i32)}
        return jax.jit(jax.grad(
            lambda p, b: transformer.loss_fn(p, b, cfg)[0])).lower(
                params, batch)
    if name.endswith(".apply"):
        params = jax.eval_shape(
            lambda: transformer.init(jax.random.PRNGKey(0), cfg))
        return jax.jit(lambda p, t: transformer.apply(p, t, cfg)).lower(
            params, jax.ShapeDtypeStruct((2, 16), i32))
    params = _params(cfg)
    if name.endswith(".paged") or name.endswith("paged_prefill_chunk"):
        state = _shapes(decode.init_paged_state(
            cfg, SLOTS, SLOTS * TOTAL // BLOCK, BLOCK, TOTAL // BLOCK))
    else:
        state = _shapes(decode.init_decode_state(cfg, SLOTS, TOTAL))
    if ".decode_step." in name:
        return decode.decode_step.lower(state, params, cfg)
    if ".decode_chunk." in name:
        return decode.decode_chunk.lower(state, params, cfg, 4)
    scalar = jax.ShapeDtypeStruct((), i32)
    if name.endswith("paged_prefill_chunk"):
        return decode.paged_prefill_chunk.lower(
            state, params, cfg, scalar, scalar,
            jax.ShapeDtypeStruct((1, 16), i32), scalar)
    rows = jax.ShapeDtypeStruct((2,), i32)
    return decode.admit_rows_and_step.lower(
        state, params, cfg, rows, jax.ShapeDtypeStruct((2, 16), i32), rows,
        rows, jax.ShapeDtypeStruct((2,), jnp.float32))


def lowered_hash(name: str) -> str:
    text = _lower(name).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_an_existing_presets_program_is_as_it_was(name):
    assert lowered_hash(name) == EXPECTED[name]


if __name__ == "__main__":
    for program in sorted(EXPECTED):
        print(f'    "{program}": "{lowered_hash(program)}",')
