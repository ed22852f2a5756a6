"""Decode dispatch: device time of the admission (prefill) modules over
the device's busy time. An admission module also carries one fused decode
step, as the program dispatches it."""

MODULE = r"^jit_admit_"


def read(run):
    from benchmarks.harness.stats import module_time

    if run["kind"] != "serve" or run.get("trace") is None:
        return None
    seconds, count = module_time(run["trace"], MODULE)
    return 100.0 * seconds / run["trace"]["busy_s"] if count else None
