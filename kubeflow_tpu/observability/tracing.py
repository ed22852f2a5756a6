"""Request-scoped tracing: ``X-Request-ID`` propagation + stream timelines.

One request id follows a request across the platform's hops: the gateway
generates it when the client didn't send one, echoes it on the response,
and forwards it upstream; the model server threads it into the continuous
decoder, which records the stream's full lifecycle as a
:class:`Timeline` — submit → queued → admitted → prefill → first token →
per-dispatch emissions → finish/error, including memory-deferral and
prefix-eviction events along the way.

Timelines land in a bounded in-memory :class:`TraceStore` ring served at
``/debug/requests`` (plain JSON, or ``?format=chrome`` for a
chrome://tracing / Perfetto - loadable trace-event file), so a slow
request's breakdown is one curl away. Spans are derived from consecutive
events, which makes the invariant the E2E test pins: the span durations
of a closed timeline sum to exactly its submit→finish wall time.

The second half of the module is the program's phase names on the
PROFILER's clock: device work is named with :func:`scope`
(``jax.named_scope`` — HLO ``op_name`` metadata, nothing at run time) and
the scheduler thread's work with :func:`host_span`
(``jax.profiler.TraceAnnotation`` — a no-op unless a profiler session is
open), so both land in the one xplane a ``jax.profiler`` capture writes.
Every name is a constant here; the program, the benchmark's readers, the
tests and the docs import or quote these and nobody spells one twice. A
``sched.*`` span carries ``round=<n>``, the number a request's
``admitted`` and ``first_token`` timeline events carry too.

The round is also the unit of the scheduler's own record: one
:class:`RoundRecord` a pass of the loop (what kind it was, what it
launched and routed, each phase's wall seconds, the thread's CPU
seconds), built by the scheduler thread from the clock reads its phases
make anyway. Under a capture its scalars ride the
``sched.round`` span; always, it lands in the decoder's :class:`RoundLog`,
two bounded rings served at ``/debug/rounds``, which also judges whether
the round was slow and says so once in the log.
"""

from __future__ import annotations

import logging
import statistics
import threading
import time
import uuid
from collections import deque

log = logging.getLogger(__name__)

REQUEST_ID_HEADER = "X-Request-ID"

# Device scopes. Outer: which dispatch phase an op belongs to.
SCOPE_PREFILL = "prefill"
SCOPE_DECODE = "decode"
SCOPE_SAMPLE = "sample"
# Inner: which part of the model. ``cast_weights`` wraps every cast of a
# parameter leaf to the compute dtype (models/transformer.py:cast_param).
SCOPE_EMBED = "embed"
SCOPE_ATTN = "attn"
SCOPE_MLP = "mlp"
SCOPE_HEAD = "head"
SCOPE_CAST_WEIGHTS = "cast_weights"
# Train step only. Backward ops keep JAX's own ``transpose(jvp(<scope>))``
# marker in the same path, so forward and backward share a scope.
SCOPE_HEAD_LOSS = "head_loss"
SCOPE_OPTIMIZER = "optimizer"
DEVICE_SCOPES = (SCOPE_PREFILL, SCOPE_DECODE, SCOPE_SAMPLE, SCOPE_EMBED,
                 SCOPE_ATTN, SCOPE_MLP, SCOPE_HEAD, SCOPE_CAST_WEIGHTS,
                 SCOPE_HEAD_LOSS, SCOPE_OPTIMIZER)
# Kernels of the hybrid mixers (models/transformer.py:mixer_types), set
# inside ``attn`` under ``decode`` or ``prefill``: the decayed linear
# attention (state update and read), the block selection over compressed
# keys (their upkeep included), and the attention over the selected blocks.
# A tuple of their own: DEVICE_SCOPES is what every model's trace holds.
SCOPE_LINEAR_ATTN = "linear_attn"
SCOPE_SPARSE_SELECT = "sparse_select"
SCOPE_SPARSE_ATTN = "sparse_attn"
MIXER_SCOPES = (SCOPE_LINEAR_ATTN, SCOPE_SPARSE_SELECT, SCOPE_SPARSE_ATTN)
# Norms only a looped stack has (models/transformer.py: ``n_passes``,
# ``post_norms``): the final norm that closes every pass and hands its
# output to the next, and a block's two after-norms (one inside each
# residual branch). A tuple of their own for the same reason.
SCOPE_LOOP_NORM = "loop_norm"
SCOPE_POST_NORM = "post_norm"
LOOP_SCOPES = (SCOPE_LOOP_NORM, SCOPE_POST_NORM)

# Host spans of the scheduler thread (serving/continuous.py:_run): one
# ``sched.round`` per pass of the loop, its children named by phase.
SPAN_PREFIX = "sched."
SPAN_ROUND = SPAN_PREFIX + "round"
SCHED_PHASES = ("idle", "plan", "build", "dispatch", "fetch", "route")
PHASE_COUNTER = "serving_scheduler_phase_seconds_total"
# A round is slow when its wall time less its ``idle`` phase is over
# SLOW_ROUND_FACTOR x the median of the recent rounds of its kind AND over
# that median by SLOW_ROUND_EXCESS_S. (Twice the median was tried on the
# chip, for the sake of 110-150 ms stalls on a 40 ms step: it also called
# the last chunk of every long admission slow, 2.05 x an interior one.)
SLOW_ROUND_FACTOR = 4.0
SLOW_ROUND_EXCESS_S = 0.025
SLOW_COUNTER = "serving_scheduler_slow_rounds_total"
# Where a slow round's excess lies when no phase holds most of it: inside
# the round and under no ``sched.<phase>`` span.
PHASE_OTHER = "other"


def scope(name: str):
    """``jax.named_scope(name)``: context manager or decorator (it runs
    while tracing, never per step). Setting one also puts op metadata into
    the persistent compile cache's key: by default JAX leaves it out, and
    an executable cached by a build that set other names (or none) would be
    loaded as it is, its stale names in every profile."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.named_scope(name)


def host_span(name: str, **args):
    """``jax.profiler.TraceAnnotation(name, **args)``: ``args`` become the
    event's stats in the xplane."""
    import jax

    return jax.profiler.TraceAnnotation(name, **args)


def gen_request_id() -> str:
    """A fresh request id (uuid4, 16 hex chars — log-greppable, collision
    odds irrelevant at ring-buffer lifetimes)."""
    return uuid.uuid4().hex[:16]


class Timeline:
    """Ordered (name, t, attrs) events for one request, t relative to
    creation. Closed timelines are immutable; ``close`` is idempotent and
    always lands the terminal event (the event cap never blocks it), so a
    closed timeline's span sum equals its duration by construction."""

    def __init__(self, request_id: str, *, max_events: int = 96,
                 on_close=None) -> None:
        self.request_id = request_id
        # Back to back, so that ``start_wall`` places the timeline on the
        # profiler's (wall) clock to within the two calls' distance.
        self.start_wall = time.time_ns() * 1e-9
        self.start = time.perf_counter()
        self.status: str | None = None  # None = still open
        self.error: str | None = None
        self._events: list[tuple[str, float, dict]] = []
        self._dropped = 0
        self._max_events = max_events
        self._lock = threading.Lock()
        self._on_close = on_close

    @property
    def open(self) -> bool:
        with self._lock:
            return self.status is None

    def event(self, name: str, **attrs) -> None:
        t = time.perf_counter() - self.start
        with self._lock:
            if self.status is not None:
                return
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            self._events.append((name, t, attrs))

    def close(self, status: str = "ok",
              error: BaseException | str | None = None, **attrs) -> None:
        t = time.perf_counter() - self.start
        with self._lock:
            if self.status is not None:
                return
            if error is not None:
                attrs["error"] = str(error)
            self._events.append(
                ("error" if error is not None else "finish", t, attrs))
            self.status = "error" if error is not None else status
            self.error = str(error) if error is not None else None
        if self._on_close is not None:
            self._on_close(self)

    def events(self) -> list[tuple[str, float, dict]]:
        with self._lock:
            return list(self._events)

    def spans(self) -> list[dict]:
        """Phase spans between consecutive events: span *i* is named by
        the event that ends it. Their durations tile first→last event, so
        ``sum(durations) == duration_s`` for a closed timeline."""
        events = self.events()
        out = []
        for (_, t0, _a), (name, t1, attrs) in zip(events, events[1:]):
            out.append({"name": name, "start_s": t0,
                        "duration_s": t1 - t0, **attrs})
        return out

    @property
    def duration_s(self) -> float:
        events = self.events()
        if len(events) < 2:
            return 0.0
        return events[-1][1] - events[0][1]

    def to_dict(self) -> dict:
        # One consistent snapshot: /debug/requests renders on an HTTP
        # thread while the decoder closes the timeline — status, error
        # and the drop count must come from the same moment.
        with self._lock:
            status = self.status
            error = self.error
            dropped = self._dropped
        events = self.events()
        return {
            "request_id": self.request_id,
            "start_unix": self.start_wall,
            "status": status or "open",
            "error": error,
            "duration_ms": round(1e3 * self.duration_s, 3),
            "dropped_events": dropped,
            "events": [
                {"name": name, "t_ms": round(1e3 * t, 3), **attrs}
                for name, t, attrs in events
            ],
            "spans": [
                {**s, "start_ms": round(1e3 * s.pop("start_s"), 3),
                 "duration_ms": round(1e3 * s.pop("duration_s"), 3)}
                for s in self.spans()
            ],
        }


class TraceStore:
    """Bounded in-memory timeline store: open timelines indexed live,
    closed ones kept in a fixed-size ring (oldest evicted first) — memory
    is bounded no matter the traffic."""

    def __init__(self, capacity: int = 256, max_events: int = 96) -> None:
        self.capacity = capacity
        self.max_events = max_events
        self._lock = threading.Lock()
        self._live: dict[int, Timeline] = {}
        self._done: deque[Timeline] = deque(maxlen=capacity)

    def start(self, request_id: str | None = None) -> Timeline:
        tl = Timeline(request_id or gen_request_id(),
                      max_events=self.max_events, on_close=self._retire)
        with self._lock:
            self._live[id(tl)] = tl
        return tl

    def _retire(self, tl: Timeline) -> None:
        with self._lock:
            self._live.pop(id(tl), None)
            self._done.append(tl)

    @property
    def open_count(self) -> int:
        with self._lock:
            return len(self._live)

    def open_timelines(self) -> list[Timeline]:
        with self._lock:
            return list(self._live.values())

    def find(self, request_id: str) -> list[dict]:
        with self._lock:
            timelines = list(self._live.values()) + list(self._done)
        return [t.to_dict() for t in timelines
                if t.request_id == request_id]

    def snapshot(self) -> dict:
        with self._lock:
            live = list(self._live.values())
            done = list(self._done)
        return {
            "open": [t.to_dict() for t in live],
            "finished": [t.to_dict() for t in done],
        }

    def chrome_trace(self) -> dict:
        """Trace-event-format export (chrome://tracing, Perfetto): one
        complete ('X') event per span, one track per request."""
        with self._lock:
            timelines = list(self._done) + list(self._live.values())
        events = []
        for tid, tl in enumerate(timelines, start=1):
            base_us = tl.start_wall * 1e6
            events.append({
                "name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                "args": {"name": f"request {tl.request_id}"},
            })
            for span in tl.spans():
                args = {k: v for k, v in span.items()
                        if k not in ("name", "start_s", "duration_s")}
                events.append({
                    "name": span["name"], "ph": "X", "pid": 1, "tid": tid,
                    "ts": base_us + span["start_s"] * 1e6,
                    "dur": span["duration_s"] * 1e6,
                    "args": args,
                })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


class RoundRecord:
    """One pass of the scheduler loop, built by the scheduler thread alone
    (no lock): ``round``, its start on the profiler's clock (``t_wall``,
    ``time.time()``, as :attr:`Timeline.start_wall`) and its ``wall_s``;
    the ``kind`` of its last dispatch; rows ``active`` at its start,
    requests ``admitted``, ``prompt_tokens`` prefilled (chunks included);
    ``phase_s``, wall seconds of each of :data:`SCHED_PHASES`;
    ``host_cpu_s``, the thread's CPU seconds over the round
    (``time.thread_time()``, read once where two rounds meet: a system
    call, and on a sandboxed host a clock that moves 10 ms at a time, so
    true of a long round or of a sum of many) — ``host_wall_s``, the
    round less the phases that block by design, less ``host_cpu_s`` is
    time the thread had work and was not running (the wait for the GIL,
    or for the machine); ``launches``, the step dispatches it enqueued
    (admission, chunk, verify, draft, decode: one XLA module execution
    each) and
    ``first_launch``, the first one's ordinal among all of this decoder's;
    ``routed`` tokens handed to streams and ``routed_late``, those whose
    gap since the stream's last token this round stretched: they came out
    of a dispatch of another kind than ``decode``, or of a decode step
    enqueued behind a chunk or a suspension; ``slow``, the phase that held
    most of the excess if :class:`RoundLog` judged the round slow, else
    ``""``, and ``median_s``, what it was judged against.

    Rounds tile the thread's time: one starts where the last one ended, so
    what falls under no phase is the round's too (:attr:`other_s`)."""

    __slots__ = ("round", "t_wall", "wall_s", "busy_s", "kind", "active",
                 "admitted", "prompt_tokens", "phase_s", "host_cpu_s",
                 "launches", "first_launch", "routed", "routed_late", "slow",
                 "median_s", "late", "_t0", "_cpu0")

    def __init__(self, round_no: int, active: int, t0: float,
                 t_wall: float, cpu0: float) -> None:
        # What close() sets is left unset until then: a record is read
        # only once its round has closed.
        self.round = round_no
        self.t_wall = t_wall
        self._t0, self._cpu0 = t0, cpu0
        self.active = active
        self.prompt_tokens = 0
        self.phase_s = dict.fromkeys(SCHED_PHASES, 0.0)
        self.launches = self.first_launch = 0
        self.routed = self.routed_late = 0
        # Whether tokens routed NOW count as late: the route phase sets it.
        self.late = False

    def launched(self, ordinal: int) -> None:
        if not self.launches:
            self.first_launch = ordinal
        self.launches += 1

    def close(self, kind: str, admitted: int, now: float,
              cpu: float) -> None:
        self.kind, self.admitted = kind, admitted
        self.wall_s = now - self._t0
        self.busy_s = self.wall_s - self.phase_s["idle"]
        self.host_cpu_s = cpu - self._cpu0
        self.slow, self.median_s = "", 0.0

    @property
    def host_wall_s(self) -> float:
        """The round less ``fetch`` and ``idle``: the four host phases
        and what lies under no phase."""
        return self.busy_s - self.phase_s["fetch"]

    @property
    def other_s(self) -> float:
        """Seconds of the round under no phase."""
        return self.wall_s - sum(self.phase_s.values())

    def span_metadata(self) -> dict:
        """The scalars the ``sched.round`` span carries as it closes
        (``slow`` as 0 or 1: the profiler drops an empty string, and the
        phase is the longest child)."""
        return {"kind": self.kind, "admitted": self.admitted,
                "prompt_tokens": self.prompt_tokens,
                "launches": self.launches, "first_launch": self.first_launch,
                "routed": self.routed, "routed_late": self.routed_late,
                "host_wall_us": int(1e6 * self.host_wall_s),
                "host_cpu_us": int(1e6 * self.host_cpu_s),
                "slow": int(bool(self.slow))}

    def to_dict(self) -> dict:
        return {
            "round": self.round, "t_unix": self.t_wall, "kind": self.kind,
            "wall_ms": round(1e3 * self.wall_s, 3),
            "phase_ms": {p: round(1e3 * s, 3)
                         for p, s in self.phase_s.items()},
            "other_ms": round(1e3 * self.other_s, 3),
            "host_wall_ms": round(1e3 * self.host_wall_s, 3),
            "host_cpu_ms": round(1e3 * self.host_cpu_s, 3),
            "active": self.active, "admitted": self.admitted,
            "prompt_tokens": self.prompt_tokens, "launches": self.launches,
            "first_launch": self.first_launch, "routed": self.routed,
            "routed_late": self.routed_late, "slow": self.slow,
            "median_ms": round(1e3 * self.median_s, 3),
        }

    def line(self) -> str:
        """The slow-round log line's body."""
        ms = {p: 1e3 * s for p, s in self.phase_s.items()}
        return (
            f"slow round {self.round} kind={self.kind} "
            f"{1e3 * self.busy_s:.1f} ms (median {1e3 * self.median_s:.1f}): "
            f"fetch={ms['fetch']:.1f} route={ms['route']:.1f} "
            f"dispatch={ms['dispatch']:.1f} build={ms['build']:.1f} "
            f"plan={ms['plan']:.1f} other={1e3 * self.other_s:.1f} "
            f"host_cpu={1e3 * self.host_cpu_s:.1f} active={self.active} "
            f"admitted={self.admitted} routed={self.routed} "
            f"t={self.t_wall:.3f}")


class _KindWindow:
    """The recent rounds of one kind and what a slow one is held to."""

    __slots__ = ("recent", "n", "median_s", "limit_s")

    def __init__(self, history: int) -> None:
        self.recent: deque[RoundRecord] = deque(maxlen=history)
        self.n = 0
        self.median_s = 0.0
        self.limit_s = float("inf")  # no median yet: nothing is slow


class RoundLog:
    """Where every :class:`RoundRecord` lands: the newest
    :attr:`CAPACITY` rounds and the newest :attr:`SLOW_CAPACITY` slow ones,
    written by the scheduler thread without a lock (a reader copies a
    ring in one C call). :meth:`add` judges a round against the median of
    the last :attr:`HISTORY` rounds of its kind that launched as many
    steps (a round that ran a chunk AND a decode step is of kind
    ``decode`` too, and many times a plain one), which it takes anew every
    :attr:`MEDIAN_EVERY` such rounds (first after :attr:`MEDIAN_FIRST`),
    so the test is one comparison a round."""

    CAPACITY = 2048
    SLOW_CAPACITY = 64
    HISTORY = 255
    MEDIAN_EVERY = 64
    MEDIAN_FIRST = 16
    LOG_EVERY_S = 1.0

    def __init__(self, slow_counter=None) -> None:
        self._recent: deque[RoundRecord] = deque(maxlen=self.CAPACITY)
        self._slow: deque[RoundRecord] = deque(maxlen=self.SLOW_CAPACITY)
        self._kinds: dict[tuple[str, int], _KindWindow] = {}
        # ``labels(phase).inc()`` of SLOW_COUNTER on the owner's registry.
        self._slow_counter = slow_counter
        self.rounds = 0
        self.slow_rounds = 0
        self.slow_seconds = 0.0   # by which slow rounds exceeded the median
        self.slow_by_phase: dict[str, int] = {}
        self._logged_at = float("-inf")
        self._unlogged = 0
        self._summarised = False

    def add(self, rec: RoundRecord) -> None:
        kind = rec.kind, rec.launches
        window = self._kinds.get(kind)
        if window is None:
            window = self._kinds[kind] = _KindWindow(self.HISTORY)
        if rec.busy_s > window.limit_s:
            self._mark_slow(rec, window)
        window.recent.append(rec)
        window.n += 1
        if window.n == self.MEDIAN_FIRST \
                or window.n % self.MEDIAN_EVERY == 0:
            median = statistics.median([r.busy_s for r in window.recent])
            window.median_s = median
            window.limit_s = max(SLOW_ROUND_FACTOR * median,
                                 median + SLOW_ROUND_EXCESS_S)
        self._recent.append(rec)
        self.rounds += 1

    def _mark_slow(self, rec: RoundRecord, window: _KindWindow) -> None:
        """Name the phase that holds most of what the round took over a
        usual round of its kind, phase by phase (medians taken here, on
        the rare path)."""
        def parts(r):
            return {**r.phase_s, PHASE_OTHER: r.other_s}

        usual = [parts(r) for r in window.recent]
        over = {phase: s - statistics.median([u[phase] for u in usual])
                for phase, s in parts(rec).items() if phase != "idle"}
        rec.slow = max(over, key=over.get)
        rec.median_s = window.median_s
        self._slow.append(rec)
        self.slow_rounds += 1
        self.slow_seconds += rec.busy_s - window.median_s
        self.slow_by_phase[rec.slow] = self.slow_by_phase.get(rec.slow, 0) + 1
        if self._slow_counter is not None:
            self._slow_counter.labels(rec.slow).inc()

    def report(self, rec: RoundRecord) -> None:
        """Log a slow round: one WARNING line, at most one a second; the
        rounds passed over are counted into the next line."""
        now = time.perf_counter()
        if now - self._logged_at < self.LOG_EVERY_S:
            self._unlogged += 1
            return
        skipped = (f" (+{self._unlogged} slow rounds not logged)"
                   if self._unlogged else "")
        self._logged_at, self._unlogged = now, 0
        log.warning("%s%s", rec.line(), skipped)

    def report_summary(self) -> None:
        """The owner's ``stop()``: one WARNING line, once, when any round
        was slow."""
        line = self.summary()
        if line and not self._summarised:
            self._summarised = True
            log.warning("%s", line)

    def summary(self) -> str | None:
        """Rounds, slow rounds by phase, the three slowest the slow ring
        still holds; None when no round was slow."""
        if not self.slow_rounds:
            return None
        by_phase = " ".join(f"{p}={n}" for p, n in sorted(
            self.slow_by_phase.items(), key=lambda kv: -kv[1]))
        slowest = "; ".join(
            f"round {r.round} kind={r.kind} {1e3 * r.busy_s:.1f} ms in "
            f"{r.slow}" for r in sorted(
                self.slow(), key=lambda r: -r.busy_s)[:3])
        return (f"scheduler rounds {self.rounds}, slow {self.slow_rounds} "
                f"({by_phase}), {self.slow_seconds:.3f} s over their "
                f"medians; slowest: {slowest}")

    def recent(self) -> list[RoundRecord]:
        return list(self._recent)

    def slow(self) -> list[RoundRecord]:
        return list(self._slow)

    def snapshot(self, slow_only: bool = False) -> dict:
        out = {"rounds_total": self.rounds, "slow_total": self.slow_rounds,
               "slow_seconds": round(self.slow_seconds, 6),
               "slow_by_phase": dict(self.slow_by_phase),
               "slow": [r.to_dict() for r in self.slow()]}
        if not slow_only:
            out["rounds"] = [r.to_dict() for r in self.recent()]
        return out

    def chrome_trace(self, slow_only: bool = False) -> dict:
        """Trace-event export: one track, a ``sched.round`` event a round
        with its phases as children. A record keeps each phase's seconds
        and not where in the round they lay, so the children are laid end
        to end from the round's start in :data:`SCHED_PHASES`' order."""
        events = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
                   "args": {"name": "scheduler rounds"}}]
        for rec in self.slow() if slow_only else self.recent():
            ts = rec.t_wall * 1e6
            args = rec.to_dict()
            del args["phase_ms"]
            events.append({"name": SPAN_ROUND, "ph": "X", "pid": 1, "tid": 1,
                           "ts": ts, "dur": rec.wall_s * 1e6, "args": args})
            for phase, secs in rec.phase_s.items():
                if secs:
                    events.append({"name": SPAN_PREFIX + phase, "ph": "X",
                                   "pid": 1, "tid": 1, "ts": ts,
                                   "dur": secs * 1e6,
                                   "args": {"round": rec.round}})
                    ts += secs * 1e6
        return {"traceEvents": events, "displayTimeUnit": "ms"}


def render_debug(store: TraceStore, query: str = "") -> tuple[bytes, str]:
    """Shared ``/debug/requests`` responder: ``(body, content_type)``.
    Plain JSON snapshot by default; ``format=chrome`` in the query string
    selects the trace-event export; ``id=<request_id>`` filters."""
    import json
    from urllib.parse import parse_qs

    params = parse_qs(query)
    if params.get("format", [""])[0] == "chrome":
        payload = store.chrome_trace()
    elif params.get("id", [""])[0]:
        payload = {"requests": store.find(params["id"][0])}
    else:
        payload = store.snapshot()
    return json.dumps(payload, indent=1).encode(), "application/json"


def render_rounds(rounds: RoundLog, query: str = "") -> tuple[bytes, str]:
    """``/debug/rounds`` responder, as :func:`render_debug`: the two rings
    as JSON; ``slow=1`` keeps the slow rounds only; ``format=chrome`` gives
    the trace-event export."""
    import json
    from urllib.parse import parse_qs

    params = parse_qs(query)
    slow_only = params.get("slow", [""])[0] not in ("", "0")
    if params.get("format", [""])[0] == "chrome":
        payload = rounds.chrome_trace(slow_only)
    else:
        payload = rounds.snapshot(slow_only)
    return json.dumps(payload, indent=1).encode(), "application/json"
