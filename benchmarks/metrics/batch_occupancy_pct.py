"""Scheduler: tokens emitted per decode step over the slots, from the
decoder's counters across the window."""


def read(run):
    c = run.get("counters")
    if not c or not c["decode_steps"]:
        return None
    return 100.0 * c["tokens_emitted"] / (c["decode_steps"] * run["slots"])
