"""The scheduler's record of a round, read back out of a trace: the
arguments a ``sched.round`` span carries as it closes (what the round
launched and routed, its host phases' wall and thread-CPU microseconds),
and the ``launch`` ordinal on every ``sched.dispatch`` and ``sched.fetch``,
which pairs a dispatch with ITS module by order where ``spans.py`` pairs it
with the nearest one.

    python3 -m benchmarks.harness.rounds <trace dir or .xplane.pb> [window s]

prints the pairing and the readings.

A launch is one call of a step function (admission, chunk, verify, draft,
decode): one XLA module execution, counted by the decoder since it was
built. Modules under other names (a block copy, the prefix cache's upkeep)
are no launches and are left out before pairing. The reductions are pure
functions on tuples, like ``spans.py``'s, so hand-made events check them;
a commit from before the arguments existed gives every reader None.
"""

from __future__ import annotations

import functools
import re
import statistics
import sys

from benchmarks.harness import spans
from benchmarks.harness.trace import MIN_GAP_S, busy_union, module_key

DISPATCH = spans.SPAN_PREFIX + "dispatch"
FETCH = spans.SPAN_PREFIX + "fetch"
# Module name → the kinds of ``sched.dispatch`` that launch it. ``draft``
# has no dispatch span of its own (a draft model proposes inside a verify
# round's ``build``): its modules hold their place in the order.
MODULE_KINDS = (
    (r"^jit_(paged_)?admit_", ("admit", "chunk")),
    (r"^jit_paged_prefill_chunk$", ("chunk",)),
    (r"^jit_verify_", ("verify",)),
    (r"^jit_extend_and_propose$", ("draft",)),
    (r"^jit_decode_(step|chunk)$", ("decode",)),
)
ADMISSION_KINDS = ("admit", "chunk")
# How many launches either way the first module of a trace may lie from the
# first ``sched.dispatch`` in it (the two planes start a little apart).
REACH = 16
# The device plane's clock is taken to read early against the host's, or
# late by no more than this: among anchorings that the kinds allow, what
# tells one from the next is a whole step of lag.
LATE_S = 1e-3


def module_kinds(name: str) -> tuple[str, ...]:
    """The dispatch kinds that launch the module, () if it is no launch."""
    key = module_key(name)
    return next((kinds for pattern, kinds in MODULE_KINDS
                 if re.search(pattern, key)), ())


# -- reductions on tuples ---------------------------------------------------


def launches(sched: list[tuple]) -> tuple[dict, dict]:
    """``({launch: (kind, start_s)}, {launch: end_s})`` of the
    ``sched.dispatch`` and ``sched.fetch`` spans that carry a ``launch``.
    Of two dispatches under one ordinal the later stands (the earlier
    launched nothing: a chunk chain that restarted)."""
    dispatched, fetched = {}, {}
    for name, start, end, args in sched:
        launch = args.get("launch")
        if not isinstance(launch, int):
            continue
        if name == DISPATCH:
            dispatched[launch] = (args.get("kind", ""), start)
        elif name == FETCH:
            fetched[launch] = end
    return dispatched, fetched


def pair(dispatched: dict, fetched: dict, modules: list[tuple],
         reach: int = REACH) -> dict | None:
    """Pair dispatches with modules BY ORDER. ``modules`` are ``(name,
    start_s, end_s)`` on the device plane's clock, launches only, in start
    order; the k-th belongs to launch ``base + k``, and ``base`` is found
    within ``reach`` of the first dispatch's ordinal: the kinds have to
    agree for every pair, and where several anchorings allow that (a trace
    of plain steps alone) the one is taken whose modules need the least
    shift to end before the ``sched.fetch`` that waited for them does.

    Returns ``base``, the number of ``pairs``, ``lag_s`` — the least shift
    of the device times that lets no module start before its own dispatch
    span opened — and ``lag_max_s``, the most that lets every module end
    before its fetch did. None without modules or dispatches. Raises,
    naming the first pair, where no anchoring makes the kinds agree."""
    if not dispatched or not modules:
        return None
    kinds = [module_kinds(name) for name, _, _ in modules]
    first = min(dispatched)
    allowed, refused = [], []
    for base in range(first - reach, first + reach + 1):
        pairs = sorted((launch, launch - base) for launch in dispatched
                       if 0 <= launch - base < len(modules))
        # Only the ends of the two planes may hold what the other lacks.
        if len(pairs) < max(1, len(dispatched) - 2 * reach):
            continue
        wrong = next(((launch, k) for launch, k in pairs
                      if dispatched[launch][0] not in kinds[k]), None)
        lower = max(dispatched[launch][1] - modules[k][1]
                    for launch, k in pairs)
        upper = min((fetched[launch] - modules[k][2] for launch, k in pairs
                     if launch in fetched), default=float("inf"))
        found = {"base": base, "pairs": len(pairs), "lower": lower,
                 "upper": upper, "wrong": wrong}
        (refused if wrong else allowed).append(found)
    if not allowed:
        if not refused:
            return None
        best = min(refused, key=lambda f: abs(f["base"] - first))
        launch, k = best["wrong"]
        raise RuntimeError(
            f"launch {launch} is a {dispatched[launch][0]!r} dispatch and "
            f"the module in its place, number {k} of the trace, is "
            f"{module_key(modules[k][0])!r}: no anchoring within {reach} "
            f"launches of {first} pairs every dispatch with a module of "
            "its kind")
    # Among the anchorings the kinds allow: the earliest modules that still
    # end before their fetch does (one launch earlier is a step more lag).
    in_time = [f for f in allowed if f["upper"] >= -LATE_S]
    best = min(in_time, key=lambda f: f["upper"]) if in_time \
        else max(allowed, key=lambda f: f["upper"])
    return {"base": best["base"], "pairs": best["pairs"],
            "lag_s": max(best["lower"], min(0.0, best["upper"])),
            "lag_max_s": best["upper"]}


def round_args(sched: list[tuple]) -> list[tuple]:
    """``(start_s, end_s, arguments)`` of the ``sched.round`` spans that
    closed inside the trace (the closing arguments are set as one ends)."""
    return [(start, end, args) for name, start, end, args in sched
            if name == spans.SPAN_ROUND and "kind" in args]


def host_ms_per_round(rounds: list[tuple]) -> tuple[float, float] | None:
    """``(host_wall_us, host_cpu_us)`` summed over the rounds, in ms a
    round: the round less its ``fetch`` and ``idle`` against the thread's
    CPU time over the WHOLE round. No metric is made of the two: on the
    v5e hosts the CPU clock moves 10 ms at a time, a read costs 6 us (so
    the program reads it once a round, not at every phase's ends), and
    ``fetch`` burns CPU of its own (0.3 ms a round in ``chat-steady``),
    so the difference reads under 0 there (``PERF.md``, PR 38). None where
    no round says."""
    told = [args for _, _, args in rounds if "host_wall_us" in args]
    if not told:
        return None
    return (1e-3 * sum(a["host_wall_us"] for a in told) / len(told),
            1e-3 * sum(a["host_cpu_us"] for a in told) / len(told))


def is_admission(args: dict) -> bool:
    """A round that prefilled something beside rows already live."""
    return (args.get("admitted", 0) > 0 or args["kind"] in ADMISSION_KINDS) \
        and args.get("active", 0) > 0


def admit_round_ms_p50(rounds: list[tuple]) -> float | None:
    walls = [1e3 * (end - start) for start, end, args in rounds
             if is_admission(args)]
    return statistics.median(walls) if walls else None


def late_token_share_pct(rounds: list[tuple]) -> float | None:
    told = [args for _, _, args in rounds if "routed" in args]
    routed = sum(a["routed"] for a in told)
    if not routed:
        return None
    return 100.0 * sum(a["routed_late"] for a in told) / routed


def idle_in_admission_s(gaps: list[tuple], rounds: list[tuple],
                        lag_s: float) -> float:
    """Seconds of device idle (``(start, end)`` gaps of the op union on
    the device's clock, moved ``lag_s`` later; pauses under 20 us are the
    device's own) inside rounds of kind ``admit`` or ``chunk``."""
    moved = [(s + lag_s, e + lag_s) for s, e in gaps if e - s >= MIN_GAP_S]
    inside = [("in", start, end) for start, end, args in rounds
              if args["kind"] in ADMISSION_KINDS]
    return spans.split_idle(moved, inside).get("in", 0.0)


# -- one trace ----------------------------------------------------------------


@functools.lru_cache(maxsize=2)
def device_timeline(path: str) -> dict | None:
    """Of the first TPU plane of an ``.xplane.pb``: ``modules``, the
    launches among its "XLA Modules" events in start order; ``others``, how
    many executions of which other modules it holds; ``gaps``, the pauses
    of the union of its op intervals. None where it holds no TPU plane."""
    planes = spans.read_planes(path)
    device = next((p for p in planes
                   if re.match(r"^/device:TPU:\d+$", p["name"])), None)
    if device is None:
        return None
    lines = {line["name"]: line for line in device["lines"]}
    events = sorted(
        ((meta["name"], start, end) for meta, start, end
         in spans._events(device, lines["XLA Modules"]) if meta),
        key=lambda e: e[1]) if "XLA Modules" in lines else []
    others: dict[str, int] = {}
    for name, _, _ in events:
        if not module_kinds(name):
            others[module_key(name)] = others.get(module_key(name), 0) + 1
    _, gaps = busy_union(
        [(start, end) for meta, start, end
         in spans._events(device, lines["XLA Ops"]) if meta]
        if "XLA Ops" in lines else [])
    return {"modules": [e for e in events if module_kinds(e[0])],
            "others": others, "gaps": gaps}


def rounds_of(run: dict) -> list[tuple] | None:
    """:func:`round_args` of the run that asks; None off the serve cells,
    without a trace, or where the program set no ``sched.*`` span."""
    reduced = spans.of_run(run, "serve")
    if reduced is None or not reduced["sched"]:
        return None
    return round_args(reduced["sched"])


def idle_in_admission_pct(run: dict) -> float | None:
    """:func:`idle_in_admission_s` of the run that asks, by the ordered
    pairing's lag, over its traced window; None where :func:`rounds_of`
    is, or where no span carries a ``launch``."""
    reduced = spans.of_run(run, "serve")
    if reduced is None:
        return None
    dispatched, fetched = launches(reduced["sched"])
    timeline = device_timeline(spans.newest_xplane()) if dispatched else None
    paired = pair(dispatched, fetched, timeline["modules"]) \
        if timeline else None
    if paired is None:
        return None
    idle = idle_in_admission_s(timeline["gaps"],
                               round_args(reduced["sched"]), paired["lag_s"])
    return 100.0 * idle / run["trace"]["window_s"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv and argv[0].endswith(".pb") \
        else spans.newest_xplane(argv[0] if argv else None)
    reduced = spans.load(path) if path else None
    if reduced is None:
        print("no .xplane.pb with a TPU plane found", file=sys.stderr)
        return 1
    rounds = round_args(reduced["sched"])
    dispatched, fetched = launches(reduced["sched"])
    timeline = device_timeline(path)
    window = max(float(argv[1]) if len(argv) > 1 else 0.0, reduced["span_s"])
    print(f"trace {reduced['path']}: {len(rounds)} closed rounds, "
          f"{len(dispatched)} dispatches with a launch "
          f"({min(dispatched, default=0)}..{max(dispatched, default=0)}), "
          f"{len(timeline['modules'])} launched modules, others "
          f"{timeline['others']}")
    paired = pair(dispatched, fetched, timeline["modules"])
    if paired is not None:
        print(f"ordered pairing: module 0 is launch {paired['base']}, "
              f"{paired['pairs']} pairs, lag {1e3 * paired['lag_s']:.3f} ms "
              f"(at most {1e3 * paired['lag_max_s']:.3f}); nearest pairing "
              f"(spans.py) {1e3 * reduced['device_clock_lag_s']:.3f} ms")
        idle = idle_in_admission_s(timeline["gaps"], rounds, paired["lag_s"])
        print(f"idle_in_admit_pct.serve {100 * idle / window:.3f} "
              f"({idle:.4f} s of {window:.4f})")
    admissions = sum(1 for _, _, a in rounds if is_admission(a))
    print(f"host wall, thread CPU (ms a round) {host_ms_per_round(rounds)}\n"
          f"admit_round_ms_p50 {admit_round_ms_p50(rounds)} over "
          f"{admissions} rounds\n"
          f"late_token_share_pct {late_token_share_pct(rounds)} "
          f"({sum(a.get('routed_late', 0) for _, _, a in rounds)} of "
          f"{sum(a.get('routed', 0) for _, _, a in rounds)} tokens)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
