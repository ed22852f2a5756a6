"""Kernels, the lightning layers' state update and read in decode: each
live row's float32 state read and written once, over the device time
under the ``linear_attn`` scope inside ``decode``."""


def read(run):
    from benchmarks.harness import hybrid
    from benchmarks.harness.device import peaks

    counted = hybrid.traced(run)
    seconds = hybrid.scope_seconds(run, ("linear_attn",))
    if counted is None or seconds is None:
        return None
    least = hybrid.linear_attn_bytes(run["config"], counted[1]) \
        / peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds
