"""Decode dispatch: device time of the ops under the program's ``prefill``
scope (the prompt forward and its insert) over the device's busy time.
Unlike ``prefill_share_pct`` it leaves out the decode step that every
admission module also carries."""


def read(run):
    from benchmarks.harness.spans import scope_share

    return scope_share(run, "serve", "prefill")
