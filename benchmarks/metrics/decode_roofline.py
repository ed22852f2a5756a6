"""Kernels, the decode step as XLA runs it: the least time the step could
take, bandwidth-bound (layer and head matrices once in bf16, K and V of
the live rows' contexts), over the device time of a decode step."""

MODULE = r"^jit_decode_step$"


def read(run):
    from benchmarks.harness.device import peaks
    from benchmarks.harness.stats import decode_step_bytes, module_time

    if run["kind"] != "serve" or run.get("trace") is None:
        return None
    seconds, count = module_time(run["trace"], MODULE)
    c = run["counters"]
    if not count or not c["decode_steps"]:
        return None
    live_rows = c["tokens_emitted"] / c["decode_steps"]
    least = decode_step_bytes(run["config"], live_rows * run["live_context"]) \
        / peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / count)
