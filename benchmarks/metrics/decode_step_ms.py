"""Decode dispatch: device time of the decode XLA module per execution,
from the trace's device plane."""

MODULE = r"^jit_decode_step$"


def read(run):
    from benchmarks.harness.stats import module_time

    if run["kind"] != "serve" or run.get("trace") is None:
        return None
    seconds, count = module_time(run["trace"], MODULE)
    return 1e3 * seconds / count if count else None
