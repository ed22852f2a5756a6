"""Finds what a workload names: the configuration, the traffic mix and the
per-layer metric readers are files found by name, so a later PR adds a
cell as new files plus new BENCHMARK.json entries and edits nothing here.
Also the one place that knows how the benchmark's plain names map onto the
program's preset and parameter tree."""

from __future__ import annotations

import importlib.util
import json
import os

from benchmarks.harness.device import REPO

BENCH = os.path.join(REPO, "benchmarks")


def benchmark_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH, kind, name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    with open(path) as f:
        return json.load(f)


def resolve(workload: str) -> dict:
    """A workload by name: its BENCHMARK.json entry, or, for one that has
    files but no entry (the knee run, the CPU rehearsal), the name split at
    its last dot into configuration and traffic."""
    bench = benchmark_json()
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    listed = entry is not None
    if entry is None:
        config, _, traffic = workload.rpartition(".")
        entry = {"name": workload, "config": config, "traffic": traffic,
                 "chips": 1}

    def reports(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        **entry, "listed": listed,
        "config_file": load_json("configs", entry["config"]),
        "traffic_file": load_json("traffic", entry["traffic"]),
        # An unlisted workload reports whatever its driver can read.
        "end_to_end": [m["name"] for m in bench["end_to_end"]
                       if not listed or reports(m)],
        "per_layer": [m["name"] for m in bench["per_layer"]
                      if not listed or reports(m)],
        "units": {m["name"]: m["unit"]
                  for m in bench["end_to_end"] + bench["per_layer"]},
    }


def metric_reader(name: str):
    """``read(run) -> float | None`` of benchmarks/metrics/<name>.py."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(names: list[str], run: dict) -> dict:
    """Every named per-layer metric whose reader finds something to read;
    a reader that returns None is left out of the line."""
    out = {}
    for name in names:
        value = metric_reader(name)(run)
        if value is not None:
            out[name] = float(value)
    return out


# -- the program's names ----------------------------------------------------


def register_preset(config: dict) -> str:
    """Register the configuration as a preset of the program at run time,
    so get_model / EngineConfig(model=name) take their normal path."""
    import jax.numpy as jnp

    from kubeflow_tpu.models import transformer

    if config["num_attention_heads"] * config["head_dim"] \
            != config["hidden_size"]:
        raise ValueError("the program derives head_dim from hidden_size")
    transformer.PRESETS[config["name"]] = transformer.TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=jnp.dtype(config["torch_dtype"]),
        **config.get("program", {}))
    return config["name"]


def program_tree(stacked: dict) -> dict:
    """The reference's stacked weights under the program's tree."""
    outer, layers = stacked["outer"], stacked["layers"]
    return {
        "embed": {"kernel": outer["embed"]},
        "layers": {
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: layers[k] for k in ("gate", "up", "down")},
            "ln_attn": layers["ln_attn"], "ln_mlp": layers["ln_mlp"],
        },
        "final_norm": outer["final_norm"],
        "lm_head": {"kernel": outer["head"]},
    }


def reference_names(tree: dict) -> dict:
    """A tree shaped like the program's parameters, flattened to
    ``{reference leaf name: value}``; a layer leaf's value keeps its
    leading layer axis."""
    layers = tree["layers"]
    out = {"embed": tree["embed"]["kernel"],
           "final_norm": tree["final_norm"],
           "head": tree["lm_head"]["kernel"],
           "ln_attn": layers["ln_attn"], "ln_mlp": layers["ln_mlp"]}
    out.update(layers["attn"])
    out.update(layers["mlp"])
    return out
