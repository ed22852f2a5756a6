"""Model step, whole, of a ``mixer_types`` configuration: forward FLOPs of
the row-steps the traced window decoded (matmuls, the lightning state's
update and read, the attended tokens and scored compressed keys of the
sparse layers) over the device time of the decode module at the chip's
bf16 peak."""


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
    return 100.0 * hybrid.decode_flops(run["config"], counted[1]) / (
        seconds * peaks(run["device"]["kind"])["bf16_flops"])
