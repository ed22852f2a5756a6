"""Overlapped input pipeline + gradient-accumulation microbatching tests:
byte-identical batch order (incl. resume), accum loss/grad parity with the
equivalent single large batch, prefetcher shutdown on every exit path, and
the stall accounting the loop's result carries."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.parallel import MeshConfig, build_mesh
from kubeflow_tpu.train.data import (
    place_batch,
    stack_microbatches,
    synthetic_batch,
    synthetic_stream,
)
from kubeflow_tpu.train.loop import RunConfig, run
from kubeflow_tpu.train.optimizers import OptimizerConfig
from kubeflow_tpu.train.prefetch import Prefetcher
from kubeflow_tpu.train.tokenstore import TokenStore, write_token_file
from kubeflow_tpu.train.trainer import build_train_step, init_state

OPT = OptimizerConfig(learning_rate=1e-2, warmup_steps=2, total_steps=50)


def _no_prefetch_threads():
    return not [t for t in threading.enumerate()
                if t.name.startswith("prefetch") and t.is_alive()]


# ---------------------------------------------------------------------------
# Prefetcher: ordering, resume, shutdown
# ---------------------------------------------------------------------------


def test_prefetcher_byte_identical_batch_sequence():
    """The overlapped pipeline yields EXACTLY the synchronous sequence."""
    model = get_model("lm-test-tiny")
    sync = synthetic_stream(model, 4, 16, seed=9)
    expected = [next(sync) for _ in range(10)]
    with Prefetcher(synthetic_stream(model, 4, 16, seed=9), None,
                    depth=3) as pre:
        for want in expected:
            got = next(pre)
            for key in want:
                np.testing.assert_array_equal(got[key], want[key])
        assert pre.batches == 10
        assert pre.host_wait_s >= 0.0
    assert _no_prefetch_threads()


def test_prefetcher_tokenstore_resume_matches_sync(tmp_path):
    """Resume at start_step through the prefetcher replays the exact
    batches the synchronous uninterrupted stream sees at those steps."""
    path = str(tmp_path / "corpus.ktpu")
    write_token_file(path, np.arange(5000, dtype=np.int32))
    with TokenStore(path) as store:
        sync = store.stream(2, 8, seed=3, start_step=0)
        full = [next(sync) for _ in range(6)]
        resumed = store.stream(2, 8, seed=3, start_step=3)
        with Prefetcher(resumed, None, depth=2) as pre:
            for want in full[3:]:
                np.testing.assert_array_equal(next(pre)["tokens"],
                                              want["tokens"])


def test_prefetcher_stream_end_raises_stopiteration():
    pre = Prefetcher(iter([{"x": np.zeros(1)}]), None, depth=2)
    next(pre)
    with pytest.raises(StopIteration):
        next(pre)
    pre.close()
    assert _no_prefetch_threads()


def test_prefetcher_propagates_producer_exception():
    def boom():
        yield {"x": np.zeros(1)}
        raise RuntimeError("synthetic corpus corruption")

    pre = Prefetcher(boom(), None, depth=2)
    next(pre)
    with pytest.raises(RuntimeError, match="corpus corruption"):
        next(pre)
    pre.close()
    assert _no_prefetch_threads()


def test_prefetcher_close_unblocks_producer_on_full_queue():
    """Preemption path: close() must stop a producer that is blocked on
    a full queue without consuming the remaining stream."""
    def infinite():
        while True:
            yield {"x": np.zeros(8)}

    pre = Prefetcher(infinite(), None, depth=1)
    deadline = time.monotonic() + 5
    while pre.qsize() < 1 and time.monotonic() < deadline:
        time.sleep(0.01)  # producer fills the queue, then blocks on put
    pre.close()
    assert _no_prefetch_threads()


def test_prefetcher_place_runs_on_producer_thread():
    placed_on = []

    def place(b):
        placed_on.append(threading.current_thread().name)
        return b

    with Prefetcher(iter([{"x": np.zeros(1)}] * 3), place, depth=2) as pre:
        for _ in range(3):
            next(pre)
    assert placed_on and all(n.startswith("prefetch") for n in placed_on)


# ---------------------------------------------------------------------------
# Loop integration: identity, stall metrics, shutdown on every exit path
# ---------------------------------------------------------------------------


def _cfg(**kw):
    base = dict(model="lm-test-tiny", mesh=MeshConfig(data=4, fsdp=2),
                optimizer=OPT, batch_size=8, seq_len=32, steps=6,
                log_every=3)
    base.update(kw)
    return RunConfig(**base)


def test_loop_prefetch_matches_synchronous_loss():
    """Prefetch on vs off: identical final loss (byte-identical batch
    order), and the stall/observability keys ride the result dict."""
    r_off = run(_cfg(prefetch=0), log=lambda *a, **k: None)
    r_on = run(_cfg(prefetch=2), log=lambda *a, **k: None)
    assert r_on["loss"] == r_off["loss"]
    for result in (r_on, r_off):
        assert 0.0 <= result["input_stall_pct"] <= 100.0
        assert result["host_wait_ms_per_step"] >= 0.0
        assert result["step_time_ema_ms"] > 0.0
    assert r_on["prefetch_depth"] == 2
    assert r_off["prefetch_depth"] == 0
    assert _no_prefetch_threads()


def test_loop_logs_stall_and_queue_depth(capsys):
    lines = []
    run(_cfg(prefetch=2), log=lines.append)
    step_lines = [ln for ln in lines if ln.startswith("step=")]
    assert step_lines
    assert all("input_stall=" in ln and "qdepth=" in ln
               for ln in step_lines)
    # Synchronous loop reports stall but has no queue.
    lines = []
    run(_cfg(prefetch=0), log=lines.append)
    step_lines = [ln for ln in lines if ln.startswith("step=")]
    assert all("input_stall=" in ln and "qdepth=" not in ln
               for ln in step_lines)


def test_loop_exception_closes_prefetcher():
    """A crash anywhere in the step loop must not leak the producer
    thread (the loop exit path ADVICE r5 #2's fix composes with)."""
    calls = []

    def exploding_log(msg):
        calls.append(msg)
        if str(msg).startswith("step="):  # a log boundary INSIDE the loop
            raise RuntimeError("log sink died")

    with pytest.raises(RuntimeError, match="log sink died"):
        run(_cfg(prefetch=2), log=exploding_log)
    assert calls  # the loop did reach a log boundary
    assert _no_prefetch_threads()


def test_loop_tokenstore_closed_after_run(tmp_path):
    path = str(tmp_path / "corpus.ktpu")
    write_token_file(path, np.arange(20000, dtype=np.int32))
    result = run(_cfg(prefetch=2, data_path=path, steps=4, log_every=2),
                 log=lambda *a, **k: None)
    assert result["step"] == 4
    assert _no_prefetch_threads()


# ---------------------------------------------------------------------------
# Gradient accumulation
# ---------------------------------------------------------------------------


def test_stack_microbatches_shapes_and_order():
    model = get_model("lm-test-tiny")
    stream = synthetic_stream(model, 2, 16, seed=4)
    ref = synthetic_stream(model, 2, 16, seed=4)
    stacked = next(stack_microbatches(stream, 3))
    assert stacked["tokens"].shape == (3, 2, 17)
    for i in range(3):
        np.testing.assert_array_equal(stacked["tokens"][i],
                                      next(ref)["tokens"])


def test_place_batch_microbatched_keeps_scan_axis_replicated():
    model = get_model("lm-test-tiny")
    mesh = build_mesh(MeshConfig(data=4, fsdp=2))
    stacked = next(stack_microbatches(
        synthetic_stream(model, 8, 16, seed=0), 2))
    placed = place_batch(stacked, mesh, model, microbatched=True)
    arr = placed["tokens"]
    assert arr.shape == (2, 8, 17)
    # Scan axis replicated; batch dim sharded over data×fsdp = 8 ways.
    assert arr.addressable_shards[0].data.shape == (2, 1, 17)


def test_accum_loss_and_grad_parity_with_single_large_batch():
    """accum_steps=k over k microbatches == one k×-large batch: same
    mean loss and, after one optimizer update, the same params (fp32
    tolerance pinned — the scan reorders the reduction)."""
    model = get_model("lm-test-tiny")
    big = synthetic_batch(model, 8, 32, seed=7)
    stacked = {k: v.reshape(4, 2, *v.shape[1:]) for k, v in big.items()}

    s_big = init_state(jax.random.PRNGKey(0), model, OPT)
    s_acc = init_state(jax.random.PRNGKey(0), model, OPT)
    step_big = build_train_step(model, OPT)
    step_acc = build_train_step(model, OPT, accum_steps=4)
    s_big, m_big = step_big(s_big, big)
    s_acc, m_acc = step_acc(s_acc, stacked)

    assert float(m_acc["loss"]) == pytest.approx(float(m_big["loss"]),
                                                 rel=1e-5)
    assert float(m_acc["grad_norm"]) == pytest.approx(
        float(m_big["grad_norm"]), rel=1e-4)
    for p_big, p_acc in zip(jax.tree.leaves(s_big.params),
                            jax.tree.leaves(s_acc.params)):
        np.testing.assert_allclose(np.asarray(p_big), np.asarray(p_acc),
                                   rtol=2e-5, atol=1e-6)
    assert int(s_acc.step) == 1  # ONE optimizer step for k microbatches


def test_accum_bf16_grad_dtype_parity_within_dtype_tolerance():
    """The deep-flagship memory recipe (grad_dtype=bfloat16) under
    accumulation: parity with the single large bf16-grad batch holds to
    bf16 tolerance, and training still reduces loss."""
    model = get_model("lm-test-tiny")
    cfg = OptimizerConfig(name="adafactor", grad_dtype="bfloat16",
                          warmup_steps=1, total_steps=8)
    big = synthetic_batch(model, 8, 32, seed=11)
    stacked = {k: v.reshape(2, 4, *v.shape[1:]) for k, v in big.items()}

    s_big = init_state(jax.random.PRNGKey(0), model, cfg)
    s_acc = init_state(jax.random.PRNGKey(0), model, cfg)
    m_big = m_acc = None
    step_big = build_train_step(model, cfg)
    step_acc = build_train_step(model, cfg, accum_steps=2)
    first = None
    for _ in range(4):
        s_big, m_big = step_big(s_big, big)
        s_acc, m_acc = step_acc(s_acc, stacked)
        if first is None:
            first = float(m_acc["loss"])
    # bf16 grads: ~8 mantissa bits → percent-level tolerance, pinned.
    assert float(m_acc["loss"]) == pytest.approx(float(m_big["loss"]),
                                                 rel=2e-2)
    assert float(m_acc["loss"]) < first
    assert all(p.dtype == jnp.float32
               for p in jax.tree.leaves(s_acc.params)
               if jnp.issubdtype(p.dtype, jnp.floating))


def test_accum_composes_with_sharded_mesh():
    """accum_steps under data×fsdp×tensor sharding: the scan axis stays
    replicated, microbatches keep the batch sharding, and parity with
    the SAME mesh's single-large-batch step holds (accumulation is the
    only variable — the model's mesh-dependent paths are held fixed)."""
    model = get_model("lm-test-tiny")
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    big = synthetic_batch(model, 8, 32, seed=13)
    stacked = {k: v.reshape(2, 4, *v.shape[1:]) for k, v in big.items()}

    s_ref = init_state(jax.random.PRNGKey(0), model, OPT, mesh)
    s_ref, m_ref = build_train_step(model, OPT, mesh)(
        s_ref, place_batch(big, mesh, model))

    state = init_state(jax.random.PRNGKey(0), model, OPT, mesh)
    step = build_train_step(model, OPT, mesh, accum_steps=2)
    placed = place_batch(stacked, mesh, model, microbatched=True)
    state, metrics = step(state, placed)
    assert float(metrics["loss"]) == pytest.approx(float(m_ref["loss"]),
                                                   rel=1e-4)
    for p_ref, p_acc in zip(jax.tree.leaves(s_ref.params),
                            jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(p_ref), np.asarray(p_acc),
                                   rtol=2e-4, atol=1e-5)
    assert int(state.step) == 1


def test_loop_accum_stream_position_is_data_exact(tmp_path):
    """An accumulating run consumes accum_steps microbatches per step and
    a resume at optimizer step N replays from microbatch N×k — the same
    data-exact contract the plain stream keeps."""
    model = get_model("lm-test-tiny")
    # The loop's stream for a resume at step 2 with accum_steps=3 ...
    resumed = stack_microbatches(
        synthetic_stream(model, 2, 16, seed=5, start_step=2 * 3), 3)
    # ... equals the uninterrupted stacked stream's third yield.
    full = stack_microbatches(
        synthetic_stream(model, 2, 16, seed=5, start_step=0), 3)
    next(full), next(full)
    np.testing.assert_array_equal(next(resumed)["tokens"],
                                  next(full)["tokens"])


def test_loop_runs_with_accum_and_prefetch():
    """The full loop with both features on: step counting, samples/sec
    accounting over the effective batch, observability keys."""
    result = run(_cfg(accum_steps=2, prefetch=2, steps=4, log_every=2),
                 log=lambda *a, **k: None)
    assert result["step"] == 4
    assert np.isfinite(result["loss"])
    assert result["accum_steps"] == 2
    assert _no_prefetch_threads()
