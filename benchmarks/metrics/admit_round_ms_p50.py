"""Scheduler: median wall time of the ``sched.round`` spans that prefilled
something beside live rows (``admitted`` > 0 or kind ``admit`` / ``chunk``,
and ``active`` > 0): the gap an admission puts between two tokens of every
row that was live."""


def read(run):
    from benchmarks.harness import rounds

    found = rounds.rounds_of(run)
    return rounds.admit_round_ms_p50(found) if found is not None else None
