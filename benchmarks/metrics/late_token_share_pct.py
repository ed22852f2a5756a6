"""Scheduler: share of the tokens routed in the traced window whose gap an
admission stretched (``routed_late`` over ``routed`` of the ``sched.round``
spans): at 5 % the cell's ``itl_p95_ms`` leaves the plain step's gap for
the admission round's."""


def read(run):
    from benchmarks.harness import rounds

    found = rounds.rounds_of(run)
    return rounds.late_token_share_pct(found) if found is not None else None
