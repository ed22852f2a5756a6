"""Scheduler: device-idle time inside the scheduler thread's own work
(``sched.plan``, ``build``, ``dispatch`` and ``route`` spans) over the
traced window: what a faster scheduler loop could give back."""


def read(run):
    from benchmarks.harness.spans import HOST_PHASES, idle_share

    return idle_share(run, HOST_PHASES)
