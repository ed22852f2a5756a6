"""Router: the experts the window's decode steps touched, summed over the
steps and the expert layers (the device's own count), over every expert
of every expert layer at every step."""


def read(run):
    from benchmarks.harness import latent_moe

    c = run.get("counters")
    cfg = run["config"]
    if not c or not cfg.get("kv_lora_rank") \
            or "experts_touched" not in c or not c["decode_steps"]:
        return None
    return 100.0 * c["experts_touched"] / (
        c["decode_steps"] * latent_moe.expert_layers(cfg)
        * cfg["n_routed_experts"])
