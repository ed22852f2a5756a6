"""Model step of a looped configuration: device time under ``loop_norm``
(the final norm that closes a pass) and ``post_norm`` (a block's two
after-norms) inside ``decode`` over the device's busy time. Small where
the norms fuse into their neighbours."""


def read(run):
    from benchmarks.harness import loop

    seconds = loop.scope_seconds(run, loop.LOOP_SCOPES)
    if seconds is None:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]
