"""The comparison that decides ``correct``: each number beside its limit."""

from __future__ import annotations

import statistics
import sys


def worst_leaf_gap(program: dict, reference: dict,
                   leave_out: frozenset = frozenset()) -> tuple[float, str]:
    """The widest gap between the program's norm and the reference's over
    the leaves, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger. Returns (gap, leaf)."""
    median = statistics.median(reference.values())
    worst, where = 0.0, ""
    for name, ref in reference.items():
        if name in leave_out:
            continue
        gap = abs(program[name] - ref) / max(ref, median)
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def relative(program: float, reference: float) -> float:
    return abs(program - reference) / abs(reference)


def decide(numbers: dict[str, float], limits: dict[str, float]) -> tuple:
    """(correct, checks): every number has to have a limit and keep to it.
    ``checks`` is ``{name: {"value", "limit"}}`` in the order given."""
    checks = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"the configuration states no limit for {name!r}")
        checks[name] = {"value": value, "limit": limits[name]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def print_checks(checks: dict, notes: dict | None = None) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        note = f"  ({notes[name]})" if notes and name in notes else ""
        print(f"check {name}: {c['value']:.6g} limit {c['limit']:.6g} "
              f"{verdict}{note}", file=sys.stderr, flush=True)
