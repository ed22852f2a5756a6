"""Train driver: the trainer's jitted step (trainer.build_train_step) on
init_state's state, as train.loop._train drives it, in a window of its own.

Set-up builds ONE compiled step with its state, fills it with the seed's
weights, drives it through its first three steps on the cell's first three
batches (these are also the warm-up), reads what the comparison needs, and
hands that same object to the window. The reference follows the same three
steps once the window has closed and the program's state is freed.
"""

from __future__ import annotations

import gc
import math
import os
import statistics
import sys
import time

import numpy as np

from benchmarks.harness import check, device, registry, traffic
from benchmarks.harness.trace import TRACE_SECONDS, TraceWindow, load
from benchmarks.reference import decoder_f32 as ref

CHECK_STEPS = 3


def _find_factored(opt_state):
    """The FactoredState inside optax's chained state."""
    if hasattr(opt_state, "v_row"):
        return opt_state
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = _find_factored(part)
            if found is not None:
                return found
    return None


def _first_grad_sq(opt_state, params):
    """Per leaf (and per layer of a stacked leaf) the squared norm of the
    first gradient as Adafactor got it, worked out from its second
    moments after one step: at step 0 they ARE mean(g^2) (decay 0)."""
    import jax.numpy as jnp

    factored = _find_factored(opt_state)
    if factored is None:
        raise RuntimeError("no factored second moments in the optimizer's "
                           "state: the cell's check reads Adafactor's")
    shapes = registry.reference_names(params)
    v_row = registry.reference_names(factored.v_row)
    v = registry.reference_names(factored.v)
    out = {}
    for name, p in shapes.items():
        stacked = name not in ref.OUTER_LEAVES
        keep = (0,) if stacked else ()
        axes = ref.factored_axes(p.shape[1:] if stacked else p.shape, 128,
                                  p.shape[0] if stacked else 0)
        if axes is not None:
            d0 = axes[1] + (1 if stacked else 0)
            reduce_over = tuple(i for i in range(v_row[name].ndim)
                                if i not in keep)
            out[name] = jnp.sum(v_row[name], axis=reduce_over) * p.shape[d0]
        else:
            reduce_over = tuple(i for i in range(p.ndim) if i not in keep)
            out[name] = jnp.sum(v[name], axis=reduce_over)
    return out


def _change_sq(params, start):
    import jax.numpy as jnp

    new, old = registry.reference_names(params), registry.reference_names(start)
    out = {}
    for name, p in new.items():
        keep = () if name in ref.OUTER_LEAVES else (0,)
        over = tuple(i for i in range(p.ndim) if i not in keep)
        out[name] = jnp.sum((p - old[name]) ** 2, axis=over)
    return out


def _per_leaf(tree: dict) -> dict[str, float]:
    """{leaf or layers.i.leaf: norm} from squared norms per program leaf."""
    out = {}
    for name, sq in tree.items():
        sq = np.asarray(sq)
        if sq.ndim == 0:
            out[name] = math.sqrt(float(sq))
        else:
            for i, x in enumerate(sq):
                out[f"layers.{i}.{name}"] = math.sqrt(float(x))
    return out


def compare(program: dict, reference: dict) -> tuple[dict, dict]:
    """The numbers the cell compares, and a note for each. Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone under Adafactor and are left out of the change."""
    median = statistics.median(reference["first_grad"].values())
    dead = frozenset(k for k, g in reference["first_grad"].items()
                     if g < 1e-3 * median)
    loss = max(check.relative(p, r) for p, r in
               zip(program["loss"], reference["loss"]))
    gnorm = max(check.relative(p, r) for p, r in
                zip(program["grad_norm"], reference["grad_norm"]))
    grad, grad_leaf = check.worst_leaf_gap(program["first_grad"],
                                           reference["first_grad"])
    change, change_leaf = check.worst_leaf_gap(program["change"],
                                               reference["change"], dead)
    return ({"loss_gap": loss, "grad_norm_gap": gnorm,
             "first_grad_leaf_gap": grad, "change_leaf_gap": change},
            {"first_grad_leaf_gap": grad_leaf,
             "change_leaf_gap": change_leaf + (
                 f"; {len(dead)} leaves left out" if dead else "")})


def control_readings(seed: int, widths, host_batches: list, trainer: dict,
                     reference: dict) -> dict:
    """What the comparison reads with the reference put in the program's
    place: computed in int8 (the control, the precision below the bf16
    the configuration states), and in float32 with half of each batch
    left out (a fault). Never part of a benchmark run."""
    out = {}
    for name, kw in (("int8", {"mode": "int8"}),
                     ("half_batch", {"half_batch": True})):
        planted = ref.train_readings(
            seed, widths, host_batches[:CHECK_STEPS], trainer["optimizer"],
            z_loss=trainer["z_loss"], **kw)
        out[name] = compare(planted, reference)[0]
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, t0: float,
        counter, control: bool = False) -> dict:
    import jax

    from kubeflow_tpu.models.registry import get_model
    from kubeflow_tpu.parallel.mesh import single_device_mesh
    from kubeflow_tpu.train.data import place_batch
    from kubeflow_tpu.train.optimizers import OptimizerConfig
    from kubeflow_tpu.train.trainer import (TrainState, build_train_step,
                                            init_state)

    config, mix = cell["config_file"], cell["traffic_file"]
    trainer = config["trainer"]
    widths = ref.Widths.from_config(config)
    model = get_model(registry.register_preset(config))
    opt_cfg = OptimizerConfig(**trainer["optimizer"])
    mesh = single_device_mesh(jax.devices()[0])

    # One object: the compiled step with its state.
    state = init_state(jax.random.PRNGKey(0), model, opt_cfg, mesh)
    weights = registry.program_tree(ref.stacked_weights(seed, widths))
    if jax.tree.structure(weights) != jax.tree.structure(state.params):
        raise RuntimeError("the program's parameter tree is not the one "
                           "registry.program_tree builds")
    params = jax.tree.map(lambda new, old: jax.device_put(
        new.astype(old.dtype), old.sharding), weights, state.params)
    state = TrainState(step=state.step, params=params,
                       opt_state=state.opt_state)
    del weights, params
    step_fn = build_train_step(model, opt_cfg, mesh)
    host_batches = getattr(traffic, mix["generator"])(
        mix, seed, config["vocab_size"])
    batches = [place_batch({"tokens": b}, mesh, model) for b in host_batches]
    tokens_per_step = mix["rows"] * mix["seq_len"]

    program = {"loss": [], "grad_norm": []}
    for k in range(CHECK_STEPS):
        state, metrics = step_fn(state, batches[k % len(batches)])
        program["loss"].append(float(metrics["loss"]))
        program["grad_norm"].append(float(metrics["grad_norm"]))
        if k == 0:
            clip = opt_cfg.grad_clip_norm
            scale = (min(1.0, clip / program["grad_norm"][0]) if clip
                     else 1.0)
            program["first_grad"] = {
                name: norm / scale for name, norm in _per_leaf(jax.jit(
                    _first_grad_sq)(state.opt_state, state.params)).items()}
    start = registry.program_tree(ref.stacked_weights(seed, widths))
    program["change"] = _per_leaf(jax.jit(_change_sq)(state.params, start))
    del start
    warm = counter.snapshot()
    print(f"set-up: {time.perf_counter() - t0:.1f} s, {CHECK_STEPS} steps "
          f"driven; compiles {warm}",
          file=sys.stderr, flush=True)

    # ---- the measured window -------------------------------------------
    tracer = None
    if trace:
        tracer = TraceWindow(
            os.path.join(device.OUT_DIR, "trace", cell["name"]),
            delay=min(1.0, seconds / 8),
            seconds=min(TRACE_SECONDS, seconds / 2))
        tracer.start()
    k = CHECK_STEPS
    stamps = []
    setup_s = time.perf_counter() - t0
    t_first = time.perf_counter()
    state, metrics = step_fn(state, batches[k % len(batches)])
    while True:
        k += 1
        # Keep one step queued behind the one whose value is fetched, as
        # the training loop does, so the device never waits for the host.
        state, ahead = step_fn(state, batches[k % len(batches)])
        last_loss = float(metrics["loss"])
        stamps.append(time.perf_counter())
        metrics = ahead
        if stamps[-1] - t_first >= seconds:
            break
    last_loss = float(metrics["loss"])
    stamps.append(time.perf_counter())
    elapsed = stamps[-1] - t_first
    if tracer is not None:
        tracer.finish()
    in_window = counter.snapshot()["compiles"] - warm["compiles"]
    peak = device.memory_peak_bytes(cell["chips"])
    steps = len(stamps)
    step_ms = [1e3 * (b - a) for a, b in zip([t_first] + stamps, stamps)]
    if not math.isfinite(last_loss):
        raise RuntimeError(f"loss is {last_loss} after {steps} steps")

    # ---- free the program, then the reference ---------------------------
    del state, metrics, ahead, batches, step_fn
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    reference = ref.train_readings(
        seed, widths, host_batches[:CHECK_STEPS], trainer["optimizer"],
        z_loss=trainer["z_loss"])
    numbers, notes = compare(program, reference)
    print(f"reference: {time.perf_counter() - t_ref:.1f} s; program loss "
          f"{program['loss']} reference {reference['loss']}; grad norm "
          f"{program['grad_norm']} reference {reference['grad_norm']}",
          file=sys.stderr, flush=True)

    reduced = load(tracer.dir, cell["chips"], tracer.window_s) \
        if tracer is not None else None
    return {
        "control": control_readings(seed, widths, host_batches, trainer,
                                    reference) if control else None,
        "attempted": steps, "failed": 0,
        "compiles_in_window": in_window,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": steps * tokens_per_step / elapsed,
                       "setup_s": setup_s},
        "numbers": numbers, "notes": notes, "limits": config["limits"],
        "earlier": {"steps": steps, "step_ms_p50": statistics.median(step_ms),
                    "step_ms_max": max(step_ms), "last_loss": last_loss,
                    "tokens_per_step": tokens_per_step},
        "run": {"kind": "train", "config": config, "mix": mix,
                "step_ms": step_ms, "steps": steps, "elapsed_s": elapsed,
                "tokens_per_step": tokens_per_step, "trace": reduced},
    }
