"""Benchmark: flagship LM training on the local accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "device", ...extras}.
``device`` is what JAX reported in the process that measured
(platform, device_kind, count); a run that finds no TPU FAILS — only
``--quick`` (the tiny presets, a CPU smoke of the plumbing whose output
is not a device measurement) runs anywhere.

Headline metric is **MFU** (model FLOPs utilization) with the standard
PaLM-appendix-B / MaxText accounting: per-token model FLOPs are
``6·N + 12·L·T_causal·W`` — parameter FLOPs plus the causal
self-attention matmuls (T_causal = (T+1)/2 average attended length,
W = attention width). The attention term is real delivered compute that
a params-only 6·N formula silently drops; at Llama-class context
(seq2048, 16 layers) it is ~6.6% of the work, so excluding it
misrepresents long-context utilization. The peak it is divided by comes
from :data:`PEAK_BF16_FLOPS`, keyed by the ``device_kind`` JAX reports;
a device that is not in the table is an error, not a default.

A chip belongs to one process at a time. A real run's parent therefore
never initialises a JAX backend: every config runs in its own child
(also the measurement's isolation, see ``run_isolated``), the child
checks for the TPU, and any child that fails fails the run.

Two training workloads run on TPU (VERDICT r2 #1 — report both the shallow
flagship and a realistic-depth model):
- ``flagship-1b``: 3 wide llama blocks, 1.13B params — the peak-MFU config.
- ``flagship-deep``: 16 llama-style layers, 1.53B params — the depth class
  users actually bring (BERT/Llama geometry); reported as ``deep_mfu_pct``
  (bs32 seq256, the BERT-class shape) plus the full sequence ladder
  (``deep_mfu_seq512_pct``, ``deep_mfu_seq1024_pct``,
  ``deep_mfu_seq2048_pct`` — the Llama-class contexts, VERDICT r3 #1).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax  # importing initialises no backend; main()'s parent never asks for one

from kubeflow_tpu.utils.jaxenv import device_summary, place_compile_cache

# Peak dense bf16 FLOP/s of ONE chip, keyed by the ``device_kind`` string
# JAX reports. Source: Google Cloud TPU documentation, "TPU v5e" system
# architecture page (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).
# Add a generation only with its documented figure and the device_kind
# seen on that hardware.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_bf16_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no documented bf16 peak for device_kind {device_kind!r}; "
            f"known: {sorted(PEAK_BF16_FLOPS)} — add it to "
            "PEAK_BF16_FLOPS with its source rather than assuming one"
        ) from None


def run_training(model_name: str, batch_size: int, seq_len: int,
                 steps: int, opt_name: str, *, grad_dtype=None,
                 trace_dir=None, overrides=None, accum_steps=1) -> dict:
    """Train ``steps`` steps; returns tok/s-per-chip, MFU and final loss
    with the device they were measured on. MFU is None where the device
    has no documented peak to divide by (the CPU, under ``--quick``).

    ``accum_steps > 1`` benchmarks gradient-accumulation microbatching:
    each optimizer step scans accum_steps microbatches of ``batch_size``
    rows — effective batch batch_size×accum at the HBM footprint of one
    microbatch, so configs whose equivalent single batch OOMs become
    feasible (and their delivered MFU measurable)."""
    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from kubeflow_tpu.train.data import place_batch, synthetic_batch
    from kubeflow_tpu.train.optimizers import OptimizerConfig
    from kubeflow_tpu.train.trainer import build_train_step, init_state

    device = device_summary()
    model = get_model(model_name, **(overrides or {}))
    n_devices = len(jax.devices())
    mesh = build_mesh(MeshConfig(data=n_devices))
    opt = OptimizerConfig(name=opt_name, warmup_steps=2,
                          total_steps=steps + 2, grad_dtype=grad_dtype)
    state = init_state(jax.random.PRNGKey(0), model, opt, mesh)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    step_fn = build_train_step(model, opt, mesh, accum_steps=accum_steps)
    host_batch = synthetic_batch(model, batch_size * accum_steps, seq_len)
    if accum_steps > 1:
        host_batch = {
            k: v.reshape(accum_steps, batch_size, *v.shape[1:])
            for k, v in host_batch.items()
        }
    batch = place_batch(host_batch, mesh, model,
                        microbatched=accum_steps > 1)

    # Warmup/compile.
    state, metrics = step_fn(state, batch)
    jax.block_until_ready(metrics["loss"])

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, batch)
    # Fetching the value waits for the last step: the timed region ends
    # when the device has finished, not when the host has enqueued.
    loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()

    tokens_per_sec = steps * batch_size * accum_steps * seq_len / dt
    per_chip = tokens_per_sec / n_devices
    # Standard MFU accounting (PaLM appendix B / MaxText): parameter
    # FLOPs (6N fwd+bwd) PLUS the causal self-attention matmuls —
    # 12 · layers · avg-attended-length · attention-width per token
    # (qk^T + att·V, forward 4·T_avg·W, training ≈ 3× forward).
    mcfg = model.config
    attn_width = getattr(mcfg, "n_heads", 0) * getattr(mcfg, "head_dim", 0)
    t_causal = (seq_len + 1) / 2
    flops_per_token = (6.0 * n_params
                       + 12.0 * mcfg.n_layers * t_causal * attn_width)
    # Release this run's buffers and executables before anything else
    # compiles in this process.
    del state, batch, step_fn, metrics
    import gc
    gc.collect()
    jax.clear_caches()
    mfu = None
    if device["platform"] == "tpu":
        mfu = (flops_per_token * per_chip
               / peak_bf16_flops(device["device_kind"]))
    return {
        "mfu": mfu,
        "device": device,
        "tokens_per_sec_per_chip": per_chip,
        "params_m": n_params / 1e6,
        "model_tflops_per_token": flops_per_token / 1e12,
        "final_loss": loss,
        "config": f"{model_name} bs{batch_size}"
                  + (f"x{accum_steps}accum" if accum_steps > 1 else "")
                  + f" seq{seq_len} {opt_name} bf16 x{n_devices}chip",
    }


def run_input_pipeline(model_name: str, batch_size: int, seq_len: int,
                       steps: int, *, prefetch: int, accum_steps: int = 1,
                       opt_name: str = "adamw") -> dict:
    """Train through the REAL input pipeline (train.loop): a fresh batch
    is synthesized and placed every step, so this measures what
    ``run_training``'s single pre-placed batch cannot — input stall.
    Returns the loop's result dict (samples_per_sec, input_stall_pct,
    host_wait_ms_per_step, loss...)."""
    from kubeflow_tpu.train.loop import RunConfig, run
    from kubeflow_tpu.train.optimizers import OptimizerConfig

    cfg = RunConfig(
        model=model_name, batch_size=batch_size, seq_len=seq_len,
        steps=steps, log_every=max(steps, 1),
        optimizer=OptimizerConfig(name=opt_name, warmup_steps=2,
                                  total_steps=steps + 2),
        prefetch=prefetch, accum_steps=accum_steps,
        graceful_shutdown=False,
    )
    result = run(cfg, log=lambda *a, **k: None)
    import gc
    gc.collect()
    jax.clear_caches()
    return result


def run_elastic(model_name: str = "lm-test-tiny", batch_size: int = 8,
                seq_len: int = 32, steps: int = 12,
                opt_name: str = "adamw") -> dict:
    """Elastic-training bench: grow half→all and shrink all→half of the
    visible devices mid-run through the REAL loop's reshard point.

    Measures per-direction remap time (``elastic_reshard_*_ms``) and full
    step-time lost to the resize (``elastic_downtime_*_ms``), and prices
    the alternative the shrink path replaces: a preempt→requeue→resume
    round (synchronous checkpoint save + restore into the target mesh +
    step rebuild, measured with the same primitives — the compute-only
    floor of the kill path, which on a real cluster also pays requeue
    backoff and pod restart). Sets the ``regression`` marker when any
    post-reshard loss differs from the undisturbed restore-into-target
    reference at the same global batch (live reshard must equal the
    rescale path it replaces, byte-for-byte), or when shrink fails to
    beat the kill-path floor for the same capacity release."""
    import re
    import shutil
    import tempfile

    from kubeflow_tpu.train import checkpoint as ckpt_lib
    from kubeflow_tpu.train.loop import RunConfig, run
    from kubeflow_tpu.train.optimizers import OptimizerConfig

    n = len(jax.devices())
    small = max(n // 2, 1)
    flip = steps // 2
    opt = OptimizerConfig(name=opt_name, warmup_steps=2,
                          total_steps=steps + 2)

    def losses_of(lines):
        out = {}
        for line in lines:
            m = re.match(r"step=(\d+) loss=(\S+)", line)
            if m:
                out[int(m.group(1))] = m.group(2)
        return out

    def drive(ck_dir, mesh_source):
        lines = []
        cfg = RunConfig(
            model=model_name, batch_size=batch_size, seq_len=seq_len,
            steps=steps, log_every=1, optimizer=opt, prefetch=2,
            graceful_shutdown=False, checkpoint_dir=ck_dir,
            checkpoint_every=10 ** 9,
        )
        result = run(cfg, log=lambda *a: lines.append(" ".join(
            str(x) for x in a)), mesh_source=mesh_source)
        return result, losses_of(lines)

    out: dict = {"metric": "elastic_reshard_ms", "unit": "ms",
                 "devices": n}
    worst_ms = 0.0
    root = tempfile.mkdtemp(prefix="bench_elastic_")
    try:
        for direction, start, target in (("grow", small, n),
                                         ("shrink", n, small)):
            ck = os.path.join(root, direction)
            fired = []

            def source(direction=direction, start=start, target=target,
                       fired=fired):
                # Flip once the loop reaches the mid-run step: the poll
                # runs before step `flip` executes, so the grant changes
                # exactly at that step boundary.
                return target if fired else start

            lines = []
            cfg = RunConfig(
                model=model_name, batch_size=batch_size, seq_len=seq_len,
                steps=steps, log_every=1, optimizer=opt, prefetch=2,
                graceful_shutdown=False, checkpoint_dir=ck,
                checkpoint_every=10 ** 9,
            )

            def log_hook(msg, lines=lines, fired=fired):
                msg = str(msg)
                lines.append(msg)
                m = re.match(r"step=(\d+) ", msg)
                if m and int(m.group(1)) >= flip:
                    fired.append(True)

            result = run(cfg, log=log_hook, mesh_source=source)
            losses = losses_of(lines)
            if result["reshard_count"] != 1:
                out["regression"] = (
                    f"{direction}: expected exactly one reshard, got "
                    f"{result['reshards']}")
                return out
            event = result["reshards"][0]
            out[f"elastic_reshard_{direction}_ms"] = round(
                1e3 * event["seconds"], 1)
            out[f"elastic_downtime_{direction}_ms"] = round(
                1e3 * event["downtime_seconds"], 1)
            worst_ms = max(worst_ms, 1e3 * event["downtime_seconds"])

            # Undisturbed reference: restore the reshard-point checkpoint
            # into the target mesh and run the tail through the same
            # loop. Prune later checkpoint steps from a copy so
            # restore_latest lands on the reshard step.
            ref_ck = os.path.join(root, f"{direction}-ref")
            shutil.copytree(ck, ref_ck)
            reshard_step = event["step"]
            for entry in os.listdir(ref_ck):
                if entry.isdigit() and int(entry) > reshard_step:
                    shutil.rmtree(os.path.join(ref_ck, entry))
            assert ckpt_lib.latest_step(ref_ck) == reshard_step
            ref_result, ref_losses = drive(ref_ck, lambda: target)
            mismatch = [
                s for s in range(reshard_step + 1, steps + 1)
                if losses.get(s) != ref_losses.get(s)]
            if mismatch or result["loss"] != ref_result["loss"]:
                out["regression"] = (
                    f"{direction}: post-reshard losses diverge from the "
                    f"restore-path reference at steps {mismatch[:4]}: "
                    f"live={[losses.get(s) for s in mismatch[:4]]} "
                    f"ref={[ref_losses.get(s) for s in mismatch[:4]]} "
                    f"final live={result['loss']} ref={ref_result['loss']}")
                return out

        # The kill path's compute-only floor for the same capacity
        # release (shrink leg): synchronous save, restore into the
        # target mesh, rebuild + recompile the step. The real path adds
        # requeue backoff and pod restart on top.
        from kubeflow_tpu.models.registry import get_model
        from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
        from kubeflow_tpu.train.data import place_batch, synthetic_batch
        from kubeflow_tpu.train.trainer import (
            build_train_step,
            init_state,
            state_shardings,
        )

        model = get_model(model_name)
        big = build_mesh(MeshConfig(data=n))
        state = init_state(jax.random.PRNGKey(0), model, opt, big)
        kill_ck = os.path.join(root, "kill")
        t0 = time.perf_counter()
        ckpt_lib.save(kill_ck, 1, state, force=True)
        target_mesh = build_mesh(MeshConfig(data=small),
                                 devices=jax.devices()[:small])
        abstract = jax.eval_shape(lambda: state)
        abstract = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=s),
            abstract, state_shardings(abstract, target_mesh, model))
        restored, _ = ckpt_lib.restore_latest(kill_ck, abstract)
        step_fn = build_train_step(model, opt, target_mesh)
        batch = place_batch(synthetic_batch(model, batch_size, seq_len),
                            target_mesh, model)
        restored, metrics = step_fn(restored, batch)
        jax.block_until_ready(metrics["loss"])
        kill_ms = 1e3 * (time.perf_counter() - t0)
        out["elastic_kill_resume_ms"] = round(kill_ms, 1)
        shrink_ms = out["elastic_downtime_shrink_ms"]
        out["elastic_shrink_vs_kill_speedup"] = round(
            kill_ms / max(shrink_ms, 1e-9), 2)
        if shrink_ms >= kill_ms:
            out["regression"] = (
                f"shrink downtime {shrink_ms}ms not better than the "
                f"kill-resume floor {kill_ms}ms")
        out["value"] = round(worst_ms, 1)
    finally:
        shutil.rmtree(root, ignore_errors=True)
        import gc
        gc.collect()
        jax.clear_caches()
    return out


def run_training_isolated(*args, _fn: str = "run_training",
                          **kwargs) -> dict:
    """A bench function (default ``run_training``) in a FRESH subprocess
    that owns the chip for its lifetime. The child checks for the TPU
    before it measures (``require_tpu``) — the parent cannot, because a
    parent that has touched the backend holds the chip and every child
    then fails. One process per config also keeps measurements
    order-independent: configs are sized to the HBM cliff, and allocator
    residue from a previous config in the same process measurably
    thrashes the next (clear_caches alone did not save the tightest
    config). A child that fails raises here, with its error text."""
    import pickle
    import subprocess
    import sys
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".pkl") as out:
        payload = pickle.dumps((_fn, args, kwargs, out.name))
        code = (
            "import pickle, sys\n"
            "fn, args, kwargs, out = pickle.loads(sys.stdin.buffer.read())\n"
            "from kubeflow_tpu.utils import jaxenv\n"
            "jaxenv.place_compile_cache()\n"
            "jaxenv.require_tpu()\n"
            "import bench\n"
            "result = getattr(bench, fn)(*args, **kwargs)\n"
            "pickle.dump(result, open(out, 'wb'))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            input=payload,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True,
        )
        if proc.returncode != 0:
            # All of it: XLA puts the cause (an HBM overflow's "Used X of
            # Y") at the head of a very long message.
            sys.stderr.write(proc.stderr.decode(errors="replace"))
            raise RuntimeError(
                f"bench subprocess {_fn}{args} {kwargs} failed "
                f"(exit {proc.returncode}); its stderr is above")
        with open(out.name, "rb") as f:
            return pickle.load(f)


def run_serving_isolated(extra_args: list[str], requests: int) -> dict:
    """One bench_serving.py run in a fresh subprocess that owns the chip;
    returns its JSON line. bench_serving itself refuses to run a
    non-``--quick`` config without a TPU. A crash, a timeout or output
    that is not JSON raises: a serving bench that did not run is a failed
    run, not a missing key."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "bench_serving.py",
         f"--requests={requests}", *extra_args],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=1800,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        raise RuntimeError(
            f"bench_serving.py {extra_args} failed "
            f"(exit {proc.returncode}); its stderr is above")
    lines = proc.stdout.strip().splitlines()
    if not lines:
        raise RuntimeError(f"bench_serving.py {extra_args} printed nothing")
    return json.loads(lines[-1])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true",
                        help="the tiny presets, in this one process, on "
                             "whatever backend JAX finds (CI smoke of the "
                             "plumbing; its output is not a device "
                             "measurement). Without it a TPU is required")
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--skip-deep", action="store_true",
                        help="flagship only (fast iteration)")
    parser.add_argument("--skip-serving", action="store_true",
                        help="training configs only (fast iteration)")
    parser.add_argument("--skip-pipeline", action="store_true",
                        help="skip the input-pipeline stall comparison")
    parser.add_argument("--serving-requests", type=int, default=40)
    parser.add_argument("--elastic", action="store_true",
                        help="elastic-training scenario only: grow/shrink "
                             "reshard latency + byte-equality + kill-path "
                             "comparison (one JSON line)")
    parser.add_argument("--trace-dir", default=None,
                        help="capture a jax.profiler trace of the timed steps")
    args = parser.parse_args(argv)

    if args.elastic:
        # One process, no children. The scenario needs a multi-chip
        # mesh; on the CPU backend carve 8 virtual devices (set BEFORE
        # any jax call initializes the backend — the flag only affects
        # the host platform, so it is inert on TPU).
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        place_compile_cache()
        out = run_elastic(steps=max(args.steps, 12))
        out["device"] = device_summary()
        print(json.dumps(out))
        return 0

    deep = deep512 = deep1024 = deep2048 = accum = None
    if args.quick:
        # One process, no children: the tiny presets on whatever backend
        # there is.
        place_compile_cache()
        flagship = run_training("lm-test-tiny", 8, 128, args.steps, "adamw",
                                trace_dir=args.trace_dir)
    else:
        # From here the parent stays off the backend: each config runs in
        # a child that owns the chip, checks it IS a TPU, and has exited
        # before the next starts (see run_training_isolated). adafactor:
        # factored slots buy model width (= MFU).
        flagship = run_training_isolated("flagship-1b", 4, 2048,
                                         args.steps, "adafactor",
                                         trace_dir=args.trace_dir)
        if not args.skip_deep:
            # Gradient accumulation at the flagship shape: effective
            # batch 32×seq2048 on a config whose equivalent SINGLE batch
            # does not fit v5e HBM (the standard flagship config already
            # sits at the bs4 memory cliff) — accumulation is the only
            # way to that effective batch at fixed slot memory.
            accum = run_training_isolated("flagship-1b", 4, 2048,
                                          args.steps, "adafactor",
                                          accum_steps=8)
            # Deep steps are ~4× faster than flagship steps; run more so
            # per-step dispatch noise amortizes out of the measurement.
            deep_steps = max(args.steps, 30)
            deep = run_training_isolated(
                "flagship-deep", 32, 256, deep_steps, "adafactor",
                grad_dtype="bfloat16")
            deep512 = run_training_isolated(
                "flagship-deep", 16, 512, deep_steps, "adafactor",
                grad_dtype="bfloat16")
            # The preset's own "llm" remat policy at every length:
            # "llm_res" (also keep the splash kernel's residuals) no
            # longer fits here — with jax 0.9.0 / libtpu 0.0.34 the
            # compile of seq1024 and seq2048 exceeds the v5e's 15.75G
            # HBM by 24M and 44M.
            deep1024 = run_training_isolated(
                "flagship-deep", 8, 1024, deep_steps, "adafactor",
                grad_dtype="bfloat16")
            deep2048 = run_training_isolated(
                "flagship-deep", 4, 2048, deep_steps, "adafactor",
                grad_dtype="bfloat16")

    def pct(run):
        # None off-TPU (--quick): no documented peak to divide by.
        return None if run["mfu"] is None else round(run["mfu"] * 100, 2)

    out = {
        "metric": "flagship_lm_train_mfu",
        "value": pct(flagship),
        "unit": "percent_of_peak_bf16",
        "device": flagship["device"],
        "tokens_per_sec_per_chip": round(
            flagship["tokens_per_sec_per_chip"], 1),
        "params_m": round(flagship["params_m"], 1),
        "model_tflops_per_sec_per_chip": round(
            flagship["model_tflops_per_token"]
            * flagship["tokens_per_sec_per_chip"], 1),
        "final_loss": round(flagship["final_loss"], 4),
        "config": flagship["config"],
    }
    if deep is not None:
        out.update({
            "deep_mfu_pct": pct(deep),
            "deep_tokens_per_sec_per_chip": round(
                deep["tokens_per_sec_per_chip"], 1),
            "deep_params_m": round(deep["params_m"], 1),
            "deep_config": deep["config"],
            "deep_mfu_seq512_pct": pct(deep512),
            "deep_mfu_seq1024_pct": pct(deep1024),
            "deep_mfu_seq2048_pct": pct(deep2048),
        })
    if accum is not None:
        out.update({
            "accum_mfu_pct": pct(accum),
            "accum_tokens_per_sec_per_chip": round(
                accum["tokens_per_sec_per_chip"], 1),
            "accum_config": accum["config"],
        })

    # Input-pipeline overlap gate: train through the REAL input path
    # (fresh batch synthesized + placed every step) with prefetch off and
    # on. Prefetch may only hide stall, never change data — batch order
    # is byte-identical by construction, so a final-loss mismatch sets
    # the regression marker the CI smoke fails on.
    if not args.skip_pipeline:
        pipe_steps = max(args.steps, 6)
        if args.quick:
            pipe_off = run_input_pipeline("lm-test-tiny", 8, 128,
                                          pipe_steps, prefetch=0)
            pipe_on = run_input_pipeline("lm-test-tiny", 8, 128,
                                         pipe_steps, prefetch=2)
        else:
            pipe_off = run_training_isolated(
                "flagship-deep", 32, 256, pipe_steps,
                _fn="run_input_pipeline", prefetch=0,
                opt_name="adafactor")
            pipe_on = run_training_isolated(
                "flagship-deep", 32, 256, pipe_steps,
                _fn="run_input_pipeline", prefetch=2,
                opt_name="adafactor")
        out.update({
            "train_input_stall_pct": pipe_on["input_stall_pct"],
            "train_input_stall_off_pct": pipe_off["input_stall_pct"],
            "train_pipeline_samples_per_sec": round(
                pipe_on["samples_per_sec"], 1),
            "train_pipeline_speedup": round(
                pipe_on["samples_per_sec"]
                / max(pipe_off["samples_per_sec"], 1e-9), 3),
        })
        if abs(pipe_on["loss"] - pipe_off["loss"]) > (
                1e-6 * max(1.0, abs(pipe_off["loss"]))):
            out["regression"] = (
                f"prefetch changed final loss: on={pipe_on['loss']} "
                f"off={pipe_off['loss']}")

    # Serving numbers ride the same driver-facing line (VERDICT r4 weak
    # #1: a claim the gate can't see is a claim the next round can
    # silently regress). Predict latency + both generation decode modes.
    if not args.quick and not args.skip_serving:
        predict = run_serving_isolated([], args.serving_requests)
        out.update({
            "serving_predict_p50_ms": predict["value"],
            "serving_predict_p99_ms": predict["p99_ms"],
            "serving_predict_config": predict["config"],
        })
        # 32 tokens as one 31-step chunk after the first (TTFT) step:
        # the chunk width D4 has yet to decide on the chip.
        gen = run_serving_isolated(
            ["--generate", "--max-new-tokens=32", "--decode-chunk=31"],
            args.serving_requests)
        out.update({
            "serving_ttft_p50_ms": gen["ttft_p50_ms"],
            "serving_fullgen_p50_ms": gen["p50_ms"],
            "serving_lockstep_fullgen_p50_ms": gen["lockstep_p50_ms"],
            "serving_continuous_vs_lockstep":
                gen["continuous_vs_lockstep"],
            "serving_decode_tokens_per_sec":
                gen["decode_tokens_per_sec"],
            "serving_mixed_p50_ms": gen["mixed_p50_ms"],
            "serving_lockstep_mixed_p50_ms": gen["lockstep_mixed_p50_ms"],
            "serving_generate_config": gen["config"],
        })
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
