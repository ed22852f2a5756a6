"""Device: share of the traced window in which no op ran on the chip."""


def read(run):
    t = run.get("trace")
    if run["kind"] != "serve" or t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
