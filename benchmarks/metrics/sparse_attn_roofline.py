"""Kernels, the sparse layers' selection and read in decode: K and V of the
attended tokens and the scored compressed keys, over the device time under
the ``sparse_select`` and ``sparse_attn`` scopes inside ``decode``."""


def read(run):
    from benchmarks.harness import hybrid
    from benchmarks.harness.device import peaks

    counted = hybrid.traced(run)
    seconds = hybrid.scope_seconds(run, ("sparse_select", "sparse_attn"))
    if counted is None or seconds is None:
        return None
    least = hybrid.sparse_attn_bytes(run["config"], counted[1]) \
        / peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds
