"""Model step: device time of the ops under the program's ``cast_weights``
scope (every cast of a parameter leaf to the compute dtype, wherever the
compiler put it) over the device's busy time. 0.0, not absent, once the
program's scopes are in the trace and none of them is a cast: weights cast
once at load leave nothing to read, and that is the reading."""


def read(run):
    from benchmarks.harness.spans import scope_share

    return scope_share(run, "serve", "cast_weights", zero_is_true=True)
