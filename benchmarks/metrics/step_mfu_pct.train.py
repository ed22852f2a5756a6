"""Train step, whole: model FLOPs per token (6 per matmul parameter plus
causal attention; the embedding lookup and recomputation do not count)
times tokens per second over the chip's bf16 peak."""


def read(run):
    from benchmarks.harness.device import peaks
    from benchmarks.harness.stats import train_flops_per_token

    if run["kind"] != "train" or run["device"]["platform"] != "tpu":
        return None
    rate = run["steps"] * run["tokens_per_step"] / run["elapsed_s"]
    flops = train_flops_per_token(run["config"], run["mix"]["seq_len"])
    return 100.0 * flops * rate / peaks(run["device"]["kind"])["bf16_flops"]
