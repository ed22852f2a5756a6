"""Model step, whole, of a latent-attention expert configuration: forward
FLOPs of the row-steps the traced window decoded
(harness/latent_moe.py:decode_flops: the absorbed latent attention over
each row's length, the routed and shared experts, the dense layer, the
head) over the device time of the decode module at the chip's bf16
peak."""


def read(run):
    from benchmarks.harness import latent_moe
    from benchmarks.harness.device import peaks
    from benchmarks.harness.stats import module_time

    marks = latent_moe.traced(run)
    if marks is None:
        return None
    seconds, count = module_time(run["trace"], latent_moe.DECODE_MODULE)
    if not count:
        return None
    flops = latent_moe.decode_flops(run["config"], marks["tokens_emitted"],
                                    marks["kv_tokens_attended"])
    return 100.0 * flops / (
        seconds * peaks(run["device"]["kind"])["bf16_flops"])
