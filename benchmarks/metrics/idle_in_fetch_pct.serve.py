"""Device: device-idle time inside the scheduler thread's ``sched.fetch``
spans (``jax.device_get`` of a step's tokens: the transfer's latency once
the step has ended) over the traced window."""


def read(run):
    from benchmarks.harness.spans import idle_share

    return idle_share(run, ("fetch",))
