"""Scheduler: host time of a scheduler round spent in the thread's own
work (``sched.plan`` + ``build`` + ``dispatch`` + ``route`` seconds over
the ``sched.round`` spans in the traced window)."""


def read(run):
    from benchmarks.harness.spans import HOST_PHASES, SPAN_ROUND, of_run

    reduced = of_run(run, "serve")
    if reduced is None or not reduced["sched"]:
        return None
    if not reduced["rounds"]:
        raise RuntimeError(
            f"no {SPAN_ROUND} span in the trace; it holds "
            f"{sorted({name for name, *_ in reduced['sched']})}")
    return 1e3 * sum(reduced["phase_s"][p] for p in HOST_PHASES) \
        / reduced["rounds"]
