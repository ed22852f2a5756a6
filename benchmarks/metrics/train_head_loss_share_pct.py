"""Train step: device time of the ops under the program's ``head_loss``
scope (head matmul and cross-entropy, forward and backward) over the
device's busy time."""


def read(run):
    from benchmarks.harness.spans import scope_share

    return scope_share(run, "train", "head_loss")
