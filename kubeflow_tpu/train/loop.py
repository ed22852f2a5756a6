"""Training loop — the entrypoint JaxJob worker pods run.

The TPU-native analogue of the reference's launcher.py (tf-controller-
examples/tf-cnn/launcher.py): read the operator-injected rendezvous env, join
the collective, build the mesh, train with periodic checkpoint, report
throughput. Runs identically on one chip, the CPU fake slice, or a multi-host
TPU slice.
"""

from __future__ import annotations

import json
import sys
import time
from dataclasses import dataclass, field

import jax

from kubeflow_tpu.models.registry import get_model
from kubeflow_tpu.observability.metrics import Histogram
from kubeflow_tpu.parallel.distributed import global_any, initialize_from_env
from kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
from kubeflow_tpu.train import checkpoint as ckpt_lib
from kubeflow_tpu.train.data import (
    place_batch,
    stack_microbatches,
    synthetic_stream,
)
from kubeflow_tpu.train.optimizers import OptimizerConfig
from kubeflow_tpu.train.prefetch import Prefetcher
from kubeflow_tpu.train.trainer import (
    build_train_step,
    init_state,
    state_shardings,
)
from kubeflow_tpu.utils.jaxenv import device_line, place_compile_cache


@dataclass
class RunConfig:
    model: str = "lm-test-tiny"
    model_overrides: dict = field(default_factory=dict)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    batch_size: int = 8
    seq_len: int = 128
    steps: int = 100
    log_every: int = 10
    # Input-pipeline overlap (train.prefetch): a producer thread
    # synthesizes/reads and places batch N+k while step N runs; `prefetch`
    # is the queue depth (0 = fully synchronous). Batch order is
    # byte-identical either way, so resume stays data-exact.
    prefetch: int = 2
    # Gradient accumulation (trainer.build_train_step): each optimizer
    # step scans `accum_steps` microbatches of `batch_size` rows —
    # effective batch batch_size×accum_steps at fixed HBM. The data
    # stream advances accum_steps microbatches per step.
    accum_steps: int = 1
    # Elastic resharding (train.elastic): poll the scheduler's placement
    # annotation every `elastic_poll_steps` steps; on a changed device
    # grant, drain the prefetcher, remap the live state onto the new
    # mesh (bit-for-bit), rebuild the jitted step and continue at the
    # same step — the data axis absorbs the resize, the global batch is
    # unchanged. 0 = fixed mesh.
    elastic_poll_steps: int = 0
    # KTPU token-corpus file (train.tokenstore); empty = synthetic data.
    data_path: str | None = None
    checkpoint_dir: str | None = None
    checkpoint_every: int = 500
    # Save asynchronously (orbax background commit) so checkpoint cadence
    # doesn't cost step time; the preemption/final save always waits.
    checkpoint_async: bool = True
    # Catch SIGTERM (the kubelet's eviction signal) and spend the grace
    # window saving a final checkpoint at the *eviction* step, so a
    # preempted run loses zero completed steps on resume (SURVEY §5.3).
    graceful_shutdown: bool = True
    seed: int = 0
    # jax.profiler trace capture (SURVEY §5.1 — the subsystem the reference
    # lacks): traces profile_steps steps starting at profile_start_step
    # (after compilation) into profile_dir, viewable in tensorboard/xprof
    # via the tensorboard manifest package.
    profile_dir: str | None = None
    profile_start_step: int = 3
    profile_steps: int = 5


def run(cfg: RunConfig, *, log=print, mesh_source=None) -> dict:
    """Train; returns final metrics {step, loss, samples_per_sec, ...}.

    ``mesh_source`` (tests/bench inject it; ``elastic_poll_steps`` builds
    the placement-annotation poller for operator-launched pods) is a
    zero-arg callable returning the current target device count, or None
    for "no signal" — the loop reshards at the next poll boundary when
    the gang-agreed target differs from the running mesh."""
    from kubeflow_tpu.train import elastic as elastic_lib

    info = initialize_from_env()
    model = get_model(cfg.model, **cfg.model_overrides)
    if mesh_source is None and cfg.elastic_poll_steps > 0:
        mesh_source = elastic_lib.placement_device_source()
    if mesh_source is not None and info.is_multislice:
        log("elastic resharding is single-slice only; ignoring the "
            "placement poller on this multislice gang")
        mesh_source = None
    # A multislice gang (MEGASCALE env) must get the hybrid DCN placement —
    # slices span the data axis; ICI-hungry axes stay within slices.
    if mesh_source is not None:
        # Elastic: the scheduler may have granted less than the max at
        # admission — the FIRST mesh already honors the grant.
        target = elastic_lib.agreed_target(mesh_source(),
                                           info.num_processes)
        n = min(target or len(jax.devices()), len(jax.devices()))
        try:
            mesh = build_mesh(
                elastic_lib.scaled_mesh_config(cfg.mesh, n),
                devices=jax.devices()[:n])
        except ValueError as e:
            log(f"ignoring initial elastic grant of {n} device(s): {e}")
            mesh = build_mesh(cfg.mesh)
    else:
        mesh = build_mesh(
            cfg.mesh,
            num_slices=info.num_slices if info.is_multislice else None,
        )
    opt_cfg = cfg.optimizer
    # Which device this run is really on (after the rendezvous: asking
    # earlier would initialise the backend before the gang has joined).
    split_axes = {a: n for a, n in mesh.shape.items() if n > 1}
    log(f"{device_line()} mesh={split_axes}")

    state = init_state(jax.random.PRNGKey(cfg.seed), model, opt_cfg, mesh)
    start_step = 0
    ckpt = None
    if cfg.checkpoint_dir:
        ckpt = ckpt_lib.Checkpointer(cfg.checkpoint_dir,
                                     async_saves=cfg.checkpoint_async)
        abstract = jax.eval_shape(lambda: state)
        abstract = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract, state_shardings(abstract, mesh, model),
        )
        restored = ckpt.restore_latest(abstract)
        if restored is not None:
            state, start_step = restored
            log(f"resumed from checkpoint step {start_step}")

    # Graceful preemption: Kubernetes evictions deliver SIGTERM with a
    # grace period — spend it finishing the in-flight step and saving.
    # (Registration only works on the main thread; library callers
    # running in a worker thread keep the default disposition. The
    # previous handler is restored on exit so a finished run doesn't
    # leave the process ignoring SIGTERM.)
    stop_requested = []
    prev_handler = None
    if cfg.graceful_shutdown:
        import signal

        def _on_sigterm(_signum, _frame):
            stop_requested.append(True)

        try:
            prev_handler = signal.getsignal(signal.SIGTERM)
            signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:
            prev_handler = None  # not the main thread

    try:
        return _train(cfg, info, model, mesh, opt_cfg, state, start_step,
                      ckpt, stop_requested, log, mesh_source=mesh_source)
    finally:
        if prev_handler is not None:
            import signal

            signal.signal(signal.SIGTERM, prev_handler)


def _make_batches(cfg, info, model, mesh, stream_step, store):
    """(batches, prefetcher) for one mesh + stream position. The stream
    is stateless in (seed, microbatch-step), so an elastic reshard
    re-anchors it here at the current position — the prefetched lookahead
    the drain discarded is re-synthesized against the NEW mesh, byte-
    identical batch order either way."""
    if store is not None:
        stream = store.stream(
            cfg.batch_size, cfg.seq_len, seed=cfg.seed,
            start_step=stream_step, shard=info.process_id,
            num_shards=info.num_processes,
        )
        if getattr(model.config, "context_parallel", False):
            # Sequence-sharded batches need seq divisible by the mesh axis:
            # ship the shifted pair, not the odd-length token array (same
            # convention as data.synthetic_batch).
            stream = (
                {"inputs": b["tokens"][:, :-1], "targets": b["tokens"][:, 1:]}
                for b in stream
            )
    else:
        stream = synthetic_stream(model, cfg.batch_size, cfg.seq_len,
                                  seed=cfg.seed, start_step=stream_step)
    if cfg.accum_steps > 1:
        stream = stack_microbatches(stream, cfg.accum_steps)

    def place(b):
        return place_batch(b, mesh, model,
                           microbatched=cfg.accum_steps > 1)

    if cfg.prefetch > 0:
        # Each process prefetches only its own shard (the stream above is
        # already per-process); placement is collective-free, so the
        # producer thread is multi-host safe.
        prefetcher = Prefetcher(stream, place, depth=cfg.prefetch)
        return prefetcher, prefetcher
    return (place(b) for b in stream), None


def _train(cfg, info, model, mesh, opt_cfg, state, start_step, ckpt,
           stop_requested, log, mesh_source=None):
    from kubeflow_tpu.train import elastic as elastic_lib

    step_fn = build_train_step(model, opt_cfg, mesh,
                               accum_steps=cfg.accum_steps)
    # The stream position counts MICROBATCHES: an accumulating run
    # resumed at optimizer step N replays from microbatch N×accum_steps —
    # data-exact resume stays stateless in (seed, step).
    store = None
    if cfg.data_path:
        from kubeflow_tpu.train.tokenstore import TokenStore

        # Stateless in (seed, step): restarting at start_step replays the
        # exact stream position — checkpoint resume is data-exact.
        store = TokenStore(cfg.data_path)
        # The numpy reader is a silent ~100x slower stand-in when g++ is
        # missing; name whichever one is feeding this run.
        log(f"token store: backend={'native' if store.native else 'numpy'} "
            f"tokens={store.n_tokens} path={cfg.data_path}")
    batches, prefetcher = _make_batches(
        cfg, info, model, mesh, start_step * cfg.accum_steps, store)
    poll_steps = (cfg.elastic_poll_steps
                  or (1 if mesh_source is not None else 0))

    # SIGTERM lands per pod at different steps, but checkpoint save is a
    # collective — under a gang the local flag is all-reduced each step
    # so every process breaks (and saves) at the SAME step.
    gang = cfg.graceful_shutdown and info.num_processes > 1

    metrics = {}
    t_start = time.perf_counter()
    t_last = t_start
    samples_per_step = cfg.batch_size * cfg.accum_steps
    samples_since = 0
    throughput = 0.0
    host_wait_total = 0.0
    host_wait_since = 0.0
    step_time_ema = None
    # Step-time distribution riding the stall accounting: the EMA hides
    # stragglers; the histogram's p50/p99 expose them (the signal a gang
    # scheduler needs to spot a slow replica).
    step_hist = Histogram()
    steps_done = 0
    profiling = False
    preempted_at = None
    reshards = []
    rejected_target = None
    try:
        for step in range(start_step, cfg.steps):
            if (mesh_source is not None and poll_steps
                    and (step - start_step) % poll_steps == 0):
                # Reshard point: the gang-agreed grant decides; the poll
                # cadence is deterministic in step, so every process
                # enters the agreement the same number of times.
                target = elastic_lib.agreed_target(mesh_source(),
                                                   info.num_processes)
                if (target and target != mesh.devices.size
                        and target != rejected_target):
                    t_rs = time.perf_counter()
                    try:
                        elastic_lib.scaled_mesh_config(cfg.mesh, target)
                        if target > len(jax.devices()):
                            raise ValueError(
                                f"only {len(jax.devices())} device(s) "
                                "visible to this process")
                    except ValueError as e:
                        rejected_target = target
                        log(f"ignoring reshard target {target}: {e}")
                    else:
                        rejected_target = None
                        # Drain in-flight prefetch BEFORE touching the
                        # state: the lookahead was placed for the old
                        # mesh; the stream re-anchors at this step.
                        if prefetcher is not None:
                            prefetcher.close()
                        if ckpt is not None:
                            # Reshard-point checkpoint: crash safety
                            # across the remap, and the restore-into-
                            # target replay the byte-equality pin
                            # compares against.
                            ckpt.save(step, state, force=True)
                            ckpt.wait()
                        mesh, state, step_fn, stats = (
                            elastic_lib.reshard_train_state(
                                state, model, opt_cfg, cfg.mesh, target,
                                accum_steps=cfg.accum_steps))
                        batches, prefetcher = _make_batches(
                            cfg, info, model, mesh,
                            step * cfg.accum_steps, store)
                        event = stats.to_dict()
                        event["step"] = step
                        event["downtime_seconds"] = round(
                            time.perf_counter() - t_rs, 6)
                        reshards.append(event)
                        log(f"resharded {stats.direction} "
                            f"{stats.from_devices}->{stats.to_devices} "
                            f"devices at step {step} in "
                            f"{stats.seconds * 1e3:.0f}ms ({stats.method})")
            t_step = time.perf_counter()
            if cfg.profile_dir and info.process_id == 0:
                if step - start_step == cfg.profile_start_step:
                    jax.profiler.start_trace(cfg.profile_dir)
                    profiling = True
                elif (profiling and
                      step - start_step ==
                      cfg.profile_start_step + cfg.profile_steps):
                    jax.profiler.stop_trace()
                    profiling = False
                    log(f"profiler trace written to {cfg.profile_dir}")
            # Host wait: time this step spent blocked on input (queue
            # wait under prefetch; synthesis + placement when
            # synchronous) — the stall the overlap exists to hide.
            t_fetch = time.perf_counter()
            batch = next(batches)
            host_wait = time.perf_counter() - t_fetch
            host_wait_total += host_wait
            host_wait_since += host_wait
            state, metrics = step_fn(state, batch)
            steps_done += 1
            samples_since += samples_per_step
            step_time = time.perf_counter() - t_step
            step_hist.observe(step_time)
            step_time_ema = (step_time if step_time_ema is None
                             else 0.9 * step_time_ema + 0.1 * step_time)
            if (step + 1) % cfg.log_every == 0 or step + 1 == cfg.steps:
                loss = float(metrics["loss"])  # sync point
                now = time.perf_counter()
                window = now - t_last
                throughput = samples_since / window
                stall_pct = 100.0 * host_wait_since / max(window, 1e-9)
                depth = (f" qdepth={prefetcher.qsize()}"
                         if prefetcher is not None else "")
                t_last, samples_since, host_wait_since = now, 0, 0.0
                log(
                    f"step={step + 1} loss={loss:.4f} "
                    f"samples/sec={throughput:.1f} "
                    f"input_stall={stall_pct:.1f}%"
                    f"{depth}"
                )
            stop_now = bool(stop_requested)
            if gang:
                stop_now = global_any(stop_now)
            if stop_now:
                # Eviction: save the just-completed step SYNCHRONOUSLY
                # (the grace window is for exactly this) so resume
                # continues from here, not from the last periodic
                # checkpoint. Under a gang, stop_now is the all-reduced
                # flag, so the save below is entered by every process at
                # the same step.
                preempted_at = step + 1
                if ckpt is not None:
                    ckpt.save(preempted_at, state, force=True)
                    ckpt.wait()
                    log(f"preempted: checkpoint saved at step "
                        f"{preempted_at}")
                break
            if ckpt is not None and (step + 1) % cfg.checkpoint_every == 0:
                ckpt.save(step + 1, state)  # async: training continues
    finally:
        # Loop exit, preemption, or an exception anywhere above: the
        # producer thread must never outlive the loop.
        if prefetcher is not None:
            prefetcher.close()
        if store is not None:
            store.close()
    total_time = time.perf_counter() - t_start
    if profiling:  # short runs: close the trace instead of dropping it
        jax.profiler.stop_trace()
        log(f"profiler trace written to {cfg.profile_dir}")
    if ckpt is not None:
        if preempted_at is None and ckpt.latest_step() != cfg.steps:
            ckpt.save(cfg.steps, state, force=True)
        ckpt.close()  # waits for pending async commits

    final_step = preempted_at if preempted_at is not None else cfg.steps
    result = {
        "step": final_step,
        "loss": float(metrics["loss"]) if metrics else None,
        "samples_per_sec": throughput,
        "process_id": info.process_id,
        "preempted": preempted_at is not None,
        # Input-stall accounting: fraction of wall time the loop sat
        # blocked on input, mean per-step host wait, and the step-time
        # EMA — the numbers that make the overlap win gated, not
        # asserted (tests/test_input_pipeline.py reads them).
        "input_stall_pct": round(
            100.0 * host_wait_total / max(total_time, 1e-9), 2),
        "host_wait_ms_per_step": round(
            1e3 * host_wait_total / max(steps_done, 1), 3),
        "step_time_ema_ms": round(1e3 * (step_time_ema or 0.0), 3),
        "step_time_p50_ms": round(1e3 * step_hist.quantile(0.5), 3),
        "step_time_p99_ms": round(1e3 * step_hist.quantile(0.99), 3),
        "prefetch_depth": cfg.prefetch,
        "accum_steps": cfg.accum_steps,
        # Elastic reshard timeline: one event per live remap (direction,
        # devices, remap seconds, full downtime incl. drain + stream
        # re-anchor) — the Timeline-style record dashboards and
        # tests/test_elastic.py read.
        "devices": int(mesh.devices.size),
        "reshard_count": len(reshards),
        "reshards": reshards,
    }
    if info.process_id == 0 and preempted_at is None:
        publish_metrics(result, log=log)
    return result


def publish_metrics(result: dict, *, client=None, environ=None, log=print):
    """Publish final metrics into the owning job's status.metrics — the path
    the study/benchmark controllers read (the reference scrapes worker logs
    with a metricsCollector CronJob instead,
    kubeflow/katib/studyjobcontroller.libsonnet:115-147). Also emits the
    log-line form for log-scraping collectors."""
    import os

    from kubeflow_tpu.apis.jobs import (
        ENV_JOB_KIND,
        ENV_JOB_NAME,
        ENV_JOB_NAMESPACE,
        JOBS_API_VERSION,
    )

    env = os.environ if environ is None else environ
    metrics = {k: v for k, v in result.items()
               if isinstance(v, (int, float)) and v is not None}
    log(f"kubeflow-tpu-metrics: {json.dumps(metrics)}")
    name = env.get(ENV_JOB_NAME)
    if not name:
        return
    ns = env.get(ENV_JOB_NAMESPACE, "default")
    kind = env.get(ENV_JOB_KIND, "JaxJob")
    if client is None:
        from kubeflow_tpu.k8s.client import HttpK8sClient

        client = HttpK8sClient()
    try:
        job = client.get(JOBS_API_VERSION, kind, name, ns)
        job.setdefault("status", {})["metrics"] = metrics
        client.update_status(job)
    except Exception as e:  # metrics publishing must never kill training
        log(f"metrics publish failed: {e}")


def main(argv=None) -> int:
    """`python -m kubeflow_tpu.train.loop '<json run config>'`"""
    import os

    argv = sys.argv[1:] if argv is None else argv
    overrides = json.loads(argv[0]) if argv else {}
    mesh_cfg = MeshConfig(**overrides.pop("mesh", {}))
    opt_cfg = OptimizerConfig(**overrides.pop("optimizer", {}))
    # Path fields honor env references ($KUBEFLOW_ARTIFACT_DIR & co.),
    # so a workflow task can target its injected artifact directory
    # without knowing the store root at authoring time.
    for key in ("checkpoint_dir", "data_path", "profile_dir"):
        if overrides.get(key):
            overrides[key] = os.path.expandvars(overrides[key])
    cfg = RunConfig(mesh=mesh_cfg, optimizer=opt_cfg, **overrides)
    print(f"compile cache: {place_compile_cache()}", flush=True)
    result = run(cfg)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
