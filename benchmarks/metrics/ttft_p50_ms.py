"""Server + scheduler: median time to first token over the requests due in
the window, from the time each was DUE; the steady statistic beside
``ttft_p95_ms``."""

import statistics


def read(run):
    if run["kind"] != "serve" or not run["ttft_ms"]:
        return None
    return statistics.median(run["ttft_ms"])
