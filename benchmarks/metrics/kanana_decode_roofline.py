"""Kernels, the decode step of a latent-attention expert configuration as
XLA runs it: the least time the traced window's steps could take,
bandwidth-bound (harness/latent_moe.py:decode_bytes: every matrix outside
the routed experts once a step, the experts each step touched once, the
latent row of every attended token), over the device time of the decode
module."""


def read(run):
    from benchmarks.harness import latent_moe
    from benchmarks.harness.stats import module_time

    marks = latent_moe.traced(run)
    if marks is None:
        return None
    seconds, count = module_time(run["trace"], latent_moe.DECODE_MODULE)
    if not count:
        return None
    return latent_moe.roofline(run, latent_moe.decode_bytes(
        run["config"], marks["decode_steps"], marks["experts_touched"],
        marks["kv_tokens_attended"]), seconds)
