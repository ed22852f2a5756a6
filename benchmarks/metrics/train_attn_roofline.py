"""Kernels, splash attention forward and backward: causal attention
FLOPs from the shapes at the bf16 peak (compute-bound) over the summed
device time of the Mosaic attention ops, per step in the trace."""

OPS = r"splash|flash_attention"
MODULE = r"^jit_(sharded_step|step_fn)$"


def read(run):
    from benchmarks.harness.device import peaks
    from benchmarks.harness.stats import (module_time, op_time,
                                          train_attention_flops)

    if run["kind"] != "train" or run.get("trace") is None:
        return None
    _, steps = module_time(run["trace"], MODULE)
    seconds = op_time(run["trace"], OPS)
    if not steps or not seconds:
        raise RuntimeError(
            f"attention op pattern {OPS!r} or step module {MODULE!r} "
            f"matches nothing in the trace: modules "
            f"{sorted(run['trace']['module_n'])}")
    flops = steps * train_attention_flops(
        run["config"], run["mix"]["rows"], run["mix"]["seq_len"])
    return 100.0 * flops / peaks(run["device"]["kind"])["bf16_flops"] / seconds
