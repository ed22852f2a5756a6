"""Server + scheduler: 95th percentile over ALL requests due in the window
of (first token at the client - the time the request was DUE on the
open-loop schedule); an unanswered request stands above every sample. A
user sees it, but 234 requests a window leave its run-to-run spread at
3-15 % on one schedule (my chip runs, PR 25), wider than a bound may be:
so it stands here, unbounded."""

from benchmarks.harness.stats import percentile


def read(run):
    if run["kind"] != "serve" or not run["ttft_ms"]:
        return None
    return percentile(run["ttft_ms"], 95, run["ttft_missing"])
