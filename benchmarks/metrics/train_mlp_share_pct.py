"""Train step: device time of the ops under the program's ``mlp`` scope
(the feed-forward matmuls and their casts, forward and backward) over the
device's busy time: the useful bulk of the step."""


def read(run):
    from benchmarks.harness.spans import scope_share

    return scope_share(run, "train", "mlp")
