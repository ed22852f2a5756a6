"""Model step of a latent-attention expert configuration: device time
under the ``moe_route`` scope inside ``decode`` (router, top-k, the sort
into groups) over the device's busy time."""


def read(run):
    from benchmarks.harness import latent_moe

    seconds = latent_moe.scope_seconds(run, ("moe_route",))
    if seconds is None:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]
