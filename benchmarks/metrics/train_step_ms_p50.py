"""Train step: median host-clock time of a step in the window (a
statistic beside the end-to-end rate, not instead of it)."""

import statistics


def read(run):
    return statistics.median(run["step_ms"]) if run["kind"] == "train" \
        else None
