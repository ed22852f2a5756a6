"""Kernels, the decode step of a looped configuration as XLA runs it: the
least time the traced window's steps could take, bandwidth-bound
(harness/loop.py:decode_bytes: the layers' matrices once a pass, the head
once, K/V of the attended tokens once), over the device time of the decode
module."""


def read(run):
    from benchmarks.harness import loop
    from benchmarks.harness.stats import module_time

    marks = loop.traced(run)
    if marks is None:
        return None
    seconds, count = module_time(run["trace"], loop.DECODE_MODULE)
    if not count:
        return None
    return loop.roofline(run, loop.decode_bytes(
        run["config"], marks["decode_steps"], marks["kv_tokens_attended"]),
        seconds)
