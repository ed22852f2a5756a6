"""Model step, whole: forward FLOPs of the prompt and output tokens the
traced window processed over the device time of the prefill and decode
modules at the chip's bf16 peak. Attention is counted at half the mean
prompt for prefill tokens and at the mean live context for decode tokens."""

MODULES = r"^jit_(admit_|decode_step$)"


def read(run):
    from benchmarks.harness.device import peaks
    from benchmarks.harness.stats import forward_flops, module_time

    marks = run.get("trace_counters")
    if run["kind"] != "serve" or run.get("trace") is None or not marks:
        return None
    seconds, count = module_time(run["trace"], MODULES)
    if not count:
        return None
    prompt, out = marks["prefill_tokens"], marks["tokens_emitted"]
    mean_prompt = prompt / max(1, marks["requests_admitted"])
    flops = forward_flops(run["config"], prompt + out,
                          prompt * mean_prompt / 2
                          + out * run["live_context"])
    return 100.0 * flops / (seconds * peaks(run["device"]["kind"])["bf16_flops"])
