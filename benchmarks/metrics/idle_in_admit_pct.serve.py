"""Scheduler: device-idle time inside ``sched.round`` spans of kind
``admit`` or ``chunk`` over the traced window, the device's times moved by
the lag of the ORDERED pairing of dispatches with modules
(``harness/rounds.py:pair``): what enqueueing an admission behind the step
in flight could win back."""


def read(run):
    from benchmarks.harness import rounds

    return rounds.idle_in_admission_pct(run)
