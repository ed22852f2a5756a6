"""Server + scheduler: 95th percentile of the wait between a request's
``queued`` and ``admitted`` events on the decoder's request timeline."""

from benchmarks.harness.stats import percentile


def read(run):
    waits = run.get("queue_wait_ms")
    return percentile(waits, 95) if waits else None
