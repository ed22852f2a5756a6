"""Kernels, the decode step of a ``mixer_types`` configuration as XLA runs
it: the least time the traced window's steps could take, bandwidth-bound
(harness/hybrid.py:decode_bytes), over the device time of the decode
module."""


def read(run):
    from benchmarks.harness import hybrid
    from benchmarks.harness.device import peaks
    from benchmarks.harness.stats import module_time

    counted = hybrid.traced(run)
    if counted is None:
        return None
    seconds, count = module_time(run["trace"], hybrid.DECODE_MODULE)
    if not count:
        return None
    marks, n = counted
    least = hybrid.decode_bytes(run["config"], marks["decode_steps"], n) \
        / peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds
