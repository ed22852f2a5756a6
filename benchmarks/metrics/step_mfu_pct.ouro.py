"""Model step, whole, of a looped configuration: forward FLOPs of the
row-steps the traced window decoded (the layers' matrices counted every
pass, the head once, the attended tokens of every cache layer) over the
device time of the decode module at the chip's bf16 peak."""


def read(run):
    from benchmarks.harness import loop
    from benchmarks.harness.device import peaks
    from benchmarks.harness.stats import module_time

    marks = loop.traced(run)
    if marks is None:
        return None
    seconds, count = module_time(run["trace"], loop.DECODE_MODULE)
    if not count:
        return None
    flops = loop.decode_flops(run["config"], marks["tokens_emitted"],
                              marks["kv_tokens_attended"])
    return 100.0 * flops / (
        seconds * peaks(run["device"]["kind"])["bf16_flops"])
