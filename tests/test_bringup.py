"""What the chip bring-up added, as far as a CPU can check it: the smoke
refuses to run without a TPU, the compile cache is placed from outside,
a trial that is a measurement refuses to run without one, and a warm that
failed says so."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import jax
import pytest

from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.serving.compile_cache import CompileCache
from kubeflow_tpu.serving.continuous import ContinuousDecoder
from kubeflow_tpu.serving.engine import EngineConfig
from kubeflow_tpu.serving.scenarios import run_trial
from kubeflow_tpu.serving.server import ModelServer
from kubeflow_tpu.utils import jaxenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, *, cwd=REPO, timeout=300, **env):
    return subprocess.run(
        cmd, cwd=cwd, capture_output=True, text=True, timeout=timeout,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------


def _no_result_line(stdout: str) -> bool:
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    return not lines or not lines[-1].startswith('{"ok"')


def test_chip_smoke_without_a_tpu_fails_and_substitutes_nothing():
    proc = _run([sys.executable, "chip_smoke.py"])
    assert proc.returncode != 0
    assert "no TPU" in proc.stdout + proc.stderr
    assert "platform='cpu'" in proc.stdout + proc.stderr
    # It stopped at the first child: no tiny preset, no server, no train.
    assert "lm-test-tiny" not in proc.stdout + proc.stderr
    assert "kubeflow_tpu.serving" not in proc.stdout
    assert "kubeflow_tpu.train.loop" not in proc.stdout
    assert _no_result_line(proc.stdout)


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                PYTHONPATH="")
    assert proc.returncode != 0
    assert _no_result_line(proc.stdout)


def test_chip_smoke_caps_file_size_and_fails_on_a_cache_write_error():
    """The driver's TPU host limits file size; the smoke holds its
    children to its own limit, and a compile-cache entry JAX could not
    write (it only warns, and leaves a truncated file) fails the phase."""
    proc = _run([sys.executable, "-c", """
import resource, chip_smoke
limit = chip_smoke.limit_file_size()
assert resource.getrlimit(resource.RLIMIT_FSIZE)[0] == limit == 64 << 20
chip_smoke.check_cache_io("p", ["step=1 loss=2.0"])
try:
    chip_smoke.check_cache_io("p", [
        "compiler.py:834: UserWarning: Error writing persistent compilation "
        "cache entry for 'jit_f': OSError: [Errno 27] File too large"])
except chip_smoke.PhaseFailed as e:
    print("failed:", e)
"""])
    assert proc.returncode == 0, proc.stderr
    assert "failed: p:" in proc.stdout and "File too large" in proc.stdout


def test_force_cpu_mesh_takes_effect_after_jax_is_imported():
    """``__graft_entry__`` imports jax at the top, and jax reads
    ``JAX_PLATFORMS`` at import: with the variable unset (a TPU host),
    ``_force_cpu_mesh`` must still keep ``dryrun_multichip`` off the chip.
    Asserted on the config, so no backend is initialised here."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import os, jax, __graft_entry__ as g\n"
         "assert jax.config.jax_platforms != 'cpu'\n"
         "g._force_cpu_mesh(8)\n"
         "from jax._src import xla_bridge\n"
         "assert not xla_bridge.backends_are_initialized()\n"
         "print(jax.config.jax_platforms, os.environ['JAX_PLATFORMS'],\n"
         "      os.environ['XLA_FLAGS'])\n"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [
        "cpu", "cpu", "--xla_force_host_platform_device_count=8"]


# ---------------------------------------------------------------------------
# The compile cache is placed from outside
# ---------------------------------------------------------------------------


@pytest.fixture()
def cache_dir_config():
    """Restore jax's cache settings: later tests must not inherit them."""
    knobs = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in knobs}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_cache_helper_leaves_an_environment_set_directory_alone(
        monkeypatch, tmp_path, cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    monkeypatch.setenv(jaxenv.CACHE_ENV, str(tmp_path / "from-env"))
    assert jaxenv.place_compile_cache() == str(tmp_path / "from-env")
    # JAX reads the variable itself; nothing was set in code.
    assert jax.config.jax_compilation_cache_dir == "sentinel"
    # Nor does the serving manifest store move XLA's cache any more.
    CompileCache(str(tmp_path / "manifests"))
    assert jax.config.jax_compilation_cache_dir == "sentinel"
    assert os.listdir(tmp_path / "manifests") == []


def test_cache_helper_defaults_to_one_fixed_path_in_the_checkout(
        monkeypatch, cache_dir_config):
    monkeypatch.delenv(jaxenv.CACHE_ENV, raising=False)
    assert jaxenv.place_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert jaxenv.place_compile_cache() == jaxenv.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == jaxenv.DEFAULT_CACHE_DIR
    # Every executable is kept, whatever it cost to compile: the serving
    # manifest books hits by dispatch key, not by compile time.
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), "must be git-ignored"


# ---------------------------------------------------------------------------
# The Experiment trial's entry: a measurement never substitutes the CPU
# ---------------------------------------------------------------------------


def test_trial_entry_prints_what_run_trial_returns():
    """What a job-mode trial's container runs: the last line of its
    output is the in-process trial's dict plus the device it ran on."""
    assignments = {"slots": 9, "kv_block_size": 12}
    proc = _run([sys.executable, "-m", "kubeflow_tpu.serving.scenarios",
                 "--scenario", "synthetic-knobs", "--seed", "5", "--quick",
                 "--assignments", json.dumps(assignments)])
    assert proc.returncode == 0, proc.stderr[-2000:]
    printed = json.loads(proc.stdout.splitlines()[-1])
    assert printed.pop("device")["platform"] == "cpu"
    assert printed == run_trial(
        "synthetic-knobs", assignments, seed=5, quick=True)


def test_trial_entry_without_quick_needs_a_tpu():
    proc = _run([sys.executable, "-m", "kubeflow_tpu.serving.scenarios",
                 "--scenario", "synthetic-knobs", "--seed", "3",
                 "--assignments", "{}"])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    # No trial ran in its place: not the tiny preset, not even the
    # closed-form scenario that needs no device.
    assert "lm-test-tiny" not in proc.stdout
    assert "objectives" not in proc.stdout


# ---------------------------------------------------------------------------
# A warm that failed says so
# ---------------------------------------------------------------------------


def test_decoder_warm_reports_a_failed_shape_and_does_not_book_it(tmp_path):
    spec = get_model("lm-test-tiny")
    params = spec.init(jax.random.PRNGKey(0), spec.config)
    dec = ContinuousDecoder(
        params, spec.config, slots=2, prefill_len=16, max_new_tokens=2,
        prefill_len_buckets=1, compile_cache_dir=str(tmp_path))
    try:
        assert dec.dispatch_keys() == ["admit:s8", "admit:s16", "decode:c1"]
        submit = dec.submit

        def failing_submit(tokens, *a, **kw):
            if len(tokens) == 8:
                raise RuntimeError("Mosaic refused this shape")
            return submit(tokens, *a, **kw)

        dec.submit = failing_submit
        report = dec.warm()
        assert report["failed"] == 1
        assert report["failed_shapes"] == ["admit:s8"]
        assert "Mosaic refused this shape" in report["first_error"]
        assert dec.metrics()["warm_failed_shapes"] == 1
        # Coverage is booked for the shapes that ran, and only those.
        booked = dec.compile_cache.load(dec.engine_fingerprint())
        assert booked == {"admit:s16", "decode:c1"}
        assert (report["hits"], report["misses"]) == (0, 2)

        dec.submit = submit
        report = dec.warm()
        assert report["failed"] == 0 and report["first_error"] is None
        assert dec.metrics()["warm_failed_shapes"] == 0
        assert dec.compile_cache.load(dec.engine_fingerprint()) == {
            "admit:s8", "admit:s16", "decode:c1"}
    finally:
        dec.stop()


def test_server_whose_warm_fails_says_so_and_ends_serve_forever():
    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=2, max_seq_len=16,
                     max_new_tokens=4),
        port=0, grpc_port=None, batch_timeout_ms=2)

    def broken_warmup():
        raise RuntimeError("compile failed on this device")

    server.engine.warmup = broken_warmup
    outcome = []

    def serve():
        try:
            server.serve_forever()
            outcome.append(None)
        except RuntimeError as e:
            outcome.append(e)

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    try:
        thread.join(timeout=60)
        assert not thread.is_alive(), "serve_forever kept serving"
        # __main__ lets this propagate: the process exits non-zero.
        assert isinstance(outcome[0], RuntimeError)
        assert "compile failed on this device" in str(outcome[0])
        assert server.warming and "compile failed" in server.warm_error
    finally:
        server.stop()


def _healthz(port: int) -> tuple[int, dict]:
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                                    timeout=5) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_healthz_answers_500_failed_once_the_warm_has_failed():
    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=2, max_seq_len=16,
                     max_new_tokens=4),
        port=0, grpc_port=None, batch_timeout_ms=2)
    server.start()
    try:
        assert _healthz(server.port) == (200, {"status": "ok"})
        server.warm_error = "RuntimeError: compile failed"
        status, body = _healthz(server.port)
        assert status == 500 and body["status"] == "failed"
        assert "compile failed" in body["error"]
    finally:
        server.stop()


def test_server_refuses_to_come_up_on_a_failed_decoder_shape(tmp_path):
    server = ModelServer(
        EngineConfig(model="lm-test-tiny", batch_size=2, max_seq_len=16,
                     max_new_tokens=2, compile_cache_dir=str(tmp_path)),
        port=0, grpc_port=None, batch_timeout_ms=2)
    try:
        decoder = server.decoder
        decoder.warm = lambda: {
            "failed": 1, "failed_shapes": ["admit:s16"],
            "first_error": "XlaRuntimeError: out of memory"}
        with pytest.raises(RuntimeError, match="admit:s16.*out of memory"):
            server.warm()
        assert server.warming
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Checkpoints under a file-size limit
# ---------------------------------------------------------------------------


def test_checkpoint_saves_under_a_file_size_limit(tmp_path, monkeypatch):
    """The driver's TPU host caps file size (RLIMIT_FSIZE) and orbax's
    2 GiB data files died there with EFBIG: an array larger than the
    limit must still save, in files under 2 x DATA_FILE_BYTES, and
    restore bit for bit."""
    import resource

    import numpy as np

    from kubeflow_tpu.train import checkpoint as ckpt_lib

    monkeypatch.setattr(ckpt_lib, "DATA_FILE_BYTES", 1 << 20)
    state = {"w": jax.numpy.asarray(np.random.default_rng(0).standard_normal(
        (4, 1024, 512), np.float32)), "step": jax.numpy.zeros((), "int32")}
    assert state["w"].nbytes == 8 << 20
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (4 << 20, hard))
    try:
        ckpt_lib.save(str(tmp_path), 1, state)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    largest = max(os.path.getsize(os.path.join(root, name))
                  for root, _, names in os.walk(tmp_path) for name in names)
    assert largest < 2 << 20
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        state)
    restored, step = ckpt_lib.restore_latest(str(tmp_path), abstract)
    assert step == 1
    np.testing.assert_array_equal(restored["w"], state["w"])
