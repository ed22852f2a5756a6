"""Model step: device time under the ``sparse_select`` and ``sparse_attn``
scopes inside ``decode`` over the device's busy time."""


def read(run):
    from benchmarks.harness import hybrid

    seconds = hybrid.scope_seconds(run, ("sparse_select", "sparse_attn"))
    if seconds is None:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]
