"""Model step of a looped configuration: device time under the ``attn``
scope inside ``decode`` over the device's busy time."""


def read(run):
    from benchmarks.harness import loop

    seconds = loop.scope_seconds(run, ("attn",))
    if seconds is None:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]
