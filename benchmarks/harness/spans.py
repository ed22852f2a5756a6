"""The program's phase names out of the profiler's xplane: device-op
seconds by scope path, the scheduler thread's ``sched.*`` spans, and the
device-idle time split among the spans that cover it.

    python3 -m benchmarks.harness.spans <trace dir or .xplane.pb> [window s]

prints the tables

Where the names live in a TPU xplane (looked at by hand, PR 26): an
"XLA Ops" event carries no ``op_name`` of its own that
``jax.profiler.ProfileData`` shows; its XEventMetadata has a ``program_id``
stat, and the ``/host:metadata`` plane holds one serialized ``HloProto`` per
program, whose instructions carry ``metadata.op_name`` (the named-scope
path). So this module reads the ``.xplane.pb`` wire format itself (both
messages are a few fields deep) and keys each op event by (program,
instruction name). An instruction the compiler made up has no ``op_name``
(XLA:TPU turns the per-layer weight casts of a layer scan into converts of
the whole stack, hoisted out of the loop, with none): it is booked to the
longest scope path among the instructions that consume its value, followed
through tuples into while-loop bodies, through a fusion's operand into the
fused computation, and through pure data movement (where the slice of the
cast stack may still say ``cast_weights``); failing that, to the path of
what it consumes. Such a ``convert`` whose source is an argument of the
program named ``params[...]`` is a weight cast whatever its consumers still
say, and gets ``cast_weights`` as its innermost scope. A fusion is booked to
its own ``op_name``, else its root's.

The reductions are pure functions on tuples, like ``trace.reduce``, so
hand-made events check them.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import sys
from collections import deque

from benchmarks.harness.device import OUT_DIR
from benchmarks.harness.trace import MIN_GAP_S, busy_union

# The program's names, quoted from kubeflow_tpu/observability/tracing.py
# and not imported: the benchmark also runs over a commit from before they
# existed (benchmarks/tests/test_spans.py holds the two to each other).
DEVICE_SCOPES = ("prefill", "decode", "sample", "embed", "attn", "mlp",
                 "head", "cast_weights", "head_loss", "optimizer")
SPAN_PREFIX = "sched."
SPAN_ROUND = "sched.round"
SCHED_PHASES = ("idle", "plan", "build", "dispatch", "fetch", "route")
HOST_PHASES = ("plan", "build", "dispatch", "route")  # the host's own work
UNSCOPED = "unscoped"
# Opcodes that move a value and compute nothing: a consumer search goes on
# through them.
MOVES = frozenset(("bitcast", "copy", "dynamic-slice", "get-tuple-element",
                   "reshape", "slice", "transpose"))
CAST = "cast_weights"
# JAX names an entry parameter after the argument's path: the weights are
# ``params[...]`` of decode.py's jitted functions, ``state.params[...]`` of
# the train step.
WEIGHT_ARGUMENT = r"(\w+\.)*params\["


# -- reductions on tuples ---------------------------------------------------


def scope_path(op_name: str) -> tuple[str, ...]:
    """The program's scopes in an HLO ``op_name``, outermost first:
    ``jit(f)/transpose(jvp(mlp))/cast_weights/convert_element_type`` →
    ``("mlp", "cast_weights")``. The last component is the primitive."""
    out = []
    for part in op_name.split("/")[:-1]:
        inner = re.fullmatch(r"(?:\w+\()*([\w.]*)\)*", part)
        if inner and inner.group(1) in DEVICE_SCOPES:
            out.append(inner.group(1))
    return tuple(out)


def self_times(events: list[tuple]) -> list[tuple]:
    """``(key, start, end)`` events of ONE line, where a ``while`` op's
    event encloses its body's → ``(key, seconds)`` with the enclosed
    events' time taken out of the enclosing one, so the parts sum to the
    union."""
    out, stack = [], []  # stack of [key, end, self seconds]

    def close(until):
        while stack and stack[-1][1] <= until:
            key, _, secs = stack.pop()
            out.append((key, secs))

    for key, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            end = min(end, stack[-1][1])
            stack[-1][2] -= end - start
        stack.append([key, end, end - start])
    close(float("inf"))
    return out


def by_scope(timed: list[tuple]) -> dict[str, float]:
    """``(scope path, seconds)`` pairs → seconds by ``"outer/inner"``
    path; an empty path is ``unscoped``."""
    out: dict[str, float] = {}
    for path, secs in timed:
        key = "/".join(path) or UNSCOPED
        out[key] = out.get(key, 0.0) + secs
    return out


def under(scope_s: dict[str, float], name: str) -> float:
    """Seconds of every path that holds scope ``name`` at any depth."""
    return sum(s for path, s in scope_s.items() if name in path.split("/"))


def innermost(scope_s: dict[str, float]) -> dict[str, float]:
    """Seconds by each path's innermost scope."""
    out: dict[str, float] = {}
    for path, s in scope_s.items():
        key = path.rsplit("/", 1)[-1]
        out[key] = out.get(key, 0.0) + s
    return out


def split_idle(gaps: list[tuple[float, float]],
               spans: list[tuple[str, float, float]]) -> dict[str, float]:
    """Device-idle ``(start, end)`` gaps intersected with host
    ``(phase, start, end)`` spans that do not overlap one another: seconds
    of idle time inside each phase, and ``uncovered`` by any. A gap that
    ``fetch`` then ``route`` cover is split between them."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out: dict[str, float] = {"uncovered": 0.0}
    for g0, g1 in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(spans) and spans[i][1] < g1:
            phase, s0, s1 = spans[i]
            overlap = min(g1, s1) - max(g0, s0)
            if overlap > 0:
                out[phase] = out.get(phase, 0.0) + overlap
                covered += overlap
            i += 1
        out["uncovered"] += (g1 - g0) - covered
    return out


def device_clock_lag(dispatches: list[float], modules: list[float],
                     reach: float = 5e-3) -> float:
    """Seconds by which the device plane's clock reads early against the
    host's, at the least: a module cannot start before the ``sched.dispatch``
    span that launched it does, so the largest (dispatch start - start of
    the module nearest after it, within ``reach``) is how far the device
    times have to move for none to. 0.0 where nothing says so."""
    modules = sorted(modules)
    lag = 0.0
    for start in dispatches:
        i = bisect.bisect_left(modules, start - reach)
        if i < len(modules) and modules[i] < start:
            lag = max(lag, start - modules[i])
    return lag


def resolve_paths(instructions: list[dict]) -> dict[str, tuple[str, ...]]:
    """Scope path per instruction name of one HLO module. Each instruction
    is ``{"name", "id", "opcode", "op_name", "operands": [ids], "called":
    [computation ids], "comp": computation id, "tuple_index",
    "parameter_number", "root": bool, "entry": bool (of the entry
    computation)}``. The rules are in the module docstring."""
    by_id = {i["id"]: i for i in instructions}
    users: dict[int, list[dict]] = {}
    roots: dict[int, dict] = {}
    # Where a value enters a called computation: a while body's
    # get-tuple-element by (computation, tuple index), a fused
    # computation's parameter by (computation, number).
    gtes: dict[tuple[int, int], list[dict]] = {}
    params: dict[tuple[int, int], list[dict]] = {}
    for ins in instructions:
        for op in ins["operands"]:
            users.setdefault(op, []).append(ins)
        if ins["root"]:
            roots[ins["comp"]] = ins
        if ins["opcode"] == "parameter":
            params.setdefault((ins["comp"], ins["parameter_number"]),
                              []).append(ins)
        if ins["opcode"] == "get-tuple-element" and ins["operands"] and \
                by_id[ins["operands"][0]]["opcode"] == "parameter":
            gtes.setdefault((ins["comp"], ins["tuple_index"]), []).append(ins)

    def own(ins):
        if ins["op_name"] or ins["opcode"] != "fusion" or not ins["called"]:
            return ins["op_name"]
        root = roots.get(ins["called"][0])
        return root["op_name"] if root else ""

    def consumers(ins):
        """The longest scope path among what consumes ``ins``'s value."""
        queue, seen, best = deque([ins]), {ins["id"]}, ()
        while queue and len(seen) < 200:
            cur = queue.popleft()
            for user in users.get(cur["id"], ()):
                if user["id"] in seen:
                    continue
                seen.add(user["id"])
                index = user["operands"].index(cur["id"])
                found = scope_path(own(user))
                if len(found) > len(best):
                    best = found
                if user["opcode"] == "tuple":
                    for loop in users.get(user["id"], ()):
                        if loop["opcode"] == "while":
                            for comp in loop["called"]:
                                queue.extend(gtes.get((comp, index), ()))
                elif user["opcode"] == "fusion" and user["called"]:
                    queue.extend(params.get((user["called"][0], index), ()))
                    root = roots.get(user["called"][0])
                    if root and root["opcode"] in MOVES:
                        queue.append(user)
                elif user["opcode"] in MOVES or not own(user):
                    queue.append(user)
        return best

    def producers(ins):
        """The scope path of the nearest named thing ``ins`` consumes."""
        queue, seen = deque([ins]), {ins["id"]}
        while queue and len(seen) < 50:
            for op in queue.popleft()["operands"]:
                if op not in seen:
                    seen.add(op)
                    if own(by_id[op]):
                        return scope_path(own(by_id[op]))
                    queue.append(by_id[op])
        return ()

    def casts_a_weight(ins):
        while ins["opcode"] in MOVES | {"convert", "copy-start", "copy-done"} \
                and ins["operands"]:
            ins = by_id[ins["operands"][0]]
        return (ins["opcode"] == "parameter" and ins["entry"]
                and re.match(WEIGHT_ARGUMENT, ins["op_name"]) is not None)

    out = {}
    for ins in instructions:
        if own(ins):
            path = scope_path(own(ins))
        else:
            path = consumers(ins) or producers(ins)
            if ins["opcode"] == "convert" and CAST not in path \
                    and casts_a_weight(ins):
                path += (CAST,)
        out[ins["name"]] = path
    return out


# -- the wire format of .xplane.pb and HloProto ------------------------------


def _varint(buf, pos: int) -> tuple[int, int]:
    value, shift = 0, 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one protobuf message: an int
    for varint and fixed fields, a memoryview for length-delimited ones."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value = int.from_bytes(buf[pos:pos + width], "little")
            pos += width
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _ints(wire: int, value) -> list[int]:
    """A repeated integer field, packed or not."""
    if wire == 0:
        return [value]
    out, pos = [], 0
    while pos < len(value):
        v, pos = _varint(value, pos)
        out.append(v)
    return out


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, stat_names: dict[int, str]):
    """One XStat → (name, value); a ``ref_value`` names its string."""
    name, value = "", None
    for number, wire, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number in (3, 4):
            value = _signed(v) if number == 4 else v
        elif number == 5:
            value = _text(v)
        elif number == 6:
            value = v
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf) -> dict:
    """One XPlane: name, lines (name, timestamp, raw events), and the event
    metadata (name, display name, stats) by id."""
    plane = {"name": "", "lines": [], "events": {}, "stats": {}}
    raw_meta, raw_lines = [], []
    for number, _, v in _fields(buf):
        if number == 2:
            plane["name"] = _text(v)
        elif number == 3:
            raw_lines.append(v)
        elif number == 4:
            raw_meta.append(v)
        elif number == 5:
            for n2, _, entry in _fields(v):
                if n2 == 2:
                    sid, sname = 0, ""
                    for n3, _, x in _fields(entry):
                        if n3 == 1:
                            sid = x
                        elif n3 == 2:
                            sname = _text(x)
                    plane["stats"][sid] = sname
    for entry in raw_meta:
        for n2, _, v in _fields(entry):
            if n2 != 2:
                continue
            meta = {"id": 0, "name": "", "display": "", "stats": {}}
            for n3, _, x in _fields(v):
                if n3 == 1:
                    meta["id"] = x
                elif n3 == 2:
                    meta["name"] = _text(x)
                elif n3 == 4:
                    meta["display"] = _text(x)
                elif n3 == 5:
                    name, value = _stat(x, plane["stats"])
                    meta["stats"][name] = value
            plane["events"][meta["id"]] = meta
    for v in raw_lines:
        line = {"name": "", "t0_ns": 0, "events": []}
        for n2, _, x in _fields(v):
            if n2 == 2:
                line["name"] = _text(x)
            elif n2 == 3:
                line["t0_ns"] = x
            elif n2 == 4:
                line["events"].append(x)
        plane["lines"].append(line)
    return plane


def _events(plane: dict, line: dict, with_stats: bool = False):
    """``(metadata, start_s, end_s[, stats])`` of a line's events."""
    for raw in line["events"]:
        meta_id = offset_ps = duration_ps = 0
        stats = {}
        for number, _, v in _fields(raw):
            if number == 1:
                meta_id = v
            elif number == 2:
                offset_ps = v
            elif number == 3:
                duration_ps = v
            elif number == 4 and with_stats:
                name, value = _stat(v, plane["stats"])
                stats[name] = value
        start = line["t0_ns"] * 1e-9 + offset_ps * 1e-12
        event = (plane["events"].get(meta_id), start,
                 start + duration_ps * 1e-12)
        yield event + (stats,) if with_stats else event


def hlo_instructions(hlo_proto) -> list[dict]:
    """The instructions of a serialized ``HloProto``, every computation's,
    as :func:`resolve_paths` takes them."""
    out = []
    for number, _, module in _fields(hlo_proto):
        if number != 1:
            continue
        entry = next((x for n2, _, x in _fields(module) if n2 == 6), None)
        for n2, _, comp in _fields(module):
            if n2 != 3:
                continue
            comp_id = root_id = 0
            mine = []
            for n3, w3, x in _fields(comp):
                if n3 == 5:
                    comp_id = x
                elif n3 == 6:
                    root_id = x
                elif n3 == 2:
                    ins = {"name": "", "id": 0, "opcode": "", "op_name": "",
                           "operands": [], "called": [], "tuple_index": 0,
                           "parameter_number": 0}
                    for n4, w4, y in _fields(x):
                        if n4 == 1:
                            ins["name"] = _text(y)
                        elif n4 == 2:
                            ins["opcode"] = _text(y)
                        elif n4 == 7:
                            for n5, _, z in _fields(y):
                                if n5 == 2:
                                    ins["op_name"] = _text(z)
                        elif n4 == 9:
                            ins["parameter_number"] = y
                        elif n4 == 13:
                            ins["tuple_index"] = y
                        elif n4 == 35:
                            ins["id"] = y
                        elif n4 == 36:
                            ins["operands"] += _ints(w4, y)
                        elif n4 == 38:
                            ins["called"] += _ints(w4, y)
                    mine.append(ins)
            for ins in mine:
                ins["comp"] = comp_id
                ins["root"] = ins["id"] == root_id
                ins["entry"] = comp_id == entry
            out += mine
    return out


# -- one trace ----------------------------------------------------------------


def newest_xplane(trace_root: str | None = None) -> str | None:
    """The newest ``.xplane.pb`` under ``benchmarks/out/trace/`` (any
    cell's): the run that asks has just written it."""
    root = trace_root or os.path.join(OUT_DIR, "trace")
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _program_id(meta: dict) -> int:
    found = re.search(r"\((\d+)\)$", meta["name"])
    return int(found.group(1)) if found else meta["id"] & (1 << 64) - 1


def read_planes(path: str) -> list[dict]:
    """The planes of an ``.xplane.pb`` as :func:`_plane` gives them."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return [_plane(v) for number, _, v in _fields(space) if number == 1]


def sched_spans(planes: list[dict]) -> list[tuple]:
    """``(name, start_s, end_s, {argument: value})`` of every ``sched.*``
    span on the host planes (any platform's trace has them)."""
    out = []
    for plane in planes:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for meta, start, end, stats in _events(plane, line, True):
                    if meta and meta["name"].startswith(SPAN_PREFIX):
                        out.append((meta["name"], start, end, stats))
    return sorted(out, key=lambda span: span[1])


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict | None:
    """Reduce one ``.xplane.pb``; None where it holds no TPU plane.

    ``scoped``: whether any instruction of any program names one of the
    program's scopes itself (a commit from before they existed names none,
    whatever :func:`resolve_paths` works out for its weight casts);
    ``scope_s``: device self-seconds by scope path (first TPU plane);
    ``op_s``: the same by (scope path, instruction) for the tables;
    ``busy_s``, ``span_s``: the union of the op intervals and the time from
    the first op's start to the last one's end; ``phase_s`` / ``idle_in_s``:
    seconds of each ``sched.*`` phase, and of device idle inside it
    (``uncovered``: inside none; ``beyond_host_plane``: before the first
    span or after the last; ``between_ops``: pauses under 20 us, the
    device's own), after the device plane's times are moved by
    ``device_clock_lag_s`` (:func:`device_clock_lag`); ``rounds``:
    ``sched.round`` spans in the trace."""
    planes = read_planes(path)
    device = next((p for p in planes
                   if re.match(r"^/device:TPU:\d+$", p["name"])), None)
    if device is None:
        return None
    ops_line = next((line for line in device["lines"]
                     if line["name"] == "XLA Ops"), None)
    if ops_line is None:
        raise RuntimeError(f"{device['name']} has no 'XLA Ops' line: "
                           f"{[line['name'] for line in device['lines']]}")
    paths: dict[int, dict] = {}
    scoped = False
    for plane in planes:
        if plane["name"] == "/host:metadata":
            for meta in plane["events"].values():
                proto = next((v for v in meta["stats"].values()
                              if isinstance(v, memoryview)), None)
                if proto is not None:
                    instructions = hlo_instructions(proto)
                    paths[_program_id(meta)] = resolve_paths(instructions)
                    scoped = scoped or any(scope_path(i["op_name"])
                                           for i in instructions)
    events = []
    for meta, start, end in _events(device, ops_line):
        if meta is None:
            continue
        program = meta["stats"].get("program_id", 0) & (1 << 64) - 1
        name = meta["display"] or meta["name"].split(" = ")[0].lstrip("%")
        events.append(((paths.get(program, {}).get(name, ()), name),
                       start, end))
    op_s: dict[tuple, float] = {}
    for key, secs in self_times(events):
        op_s[key] = op_s.get(key, 0.0) + secs
    scope_s = by_scope([(scopes, secs) for (scopes, _), secs in op_s.items()])
    busy, gaps = busy_union([(s, e) for _, s, e in events])
    sched = sched_spans(planes)
    modules = next((line for line in device["lines"]
                    if line["name"] == "XLA Modules"), None)
    lag = device_clock_lag(
        [s for name, s, _, _ in sched if name == SPAN_PREFIX + "dispatch"],
        [s for _, s, _ in _events(device, modules)] if modules else [])
    gaps = [(s + lag, e + lag) for s, e in gaps]
    phases = [(name[len(SPAN_PREFIX):], s, e) for name, s, e, _ in sched
              if name != SPAN_ROUND]
    phase_s = {p: 0.0 for p in SCHED_PHASES}
    for phase, s, e in phases:
        phase_s[phase] = phase_s.get(phase, 0.0) + (e - s)
    # The host plane starts and stops a little apart from the device
    # plane: idle time beyond its spans cannot be given to any.
    host_0 = min((s for _, s, _ in phases), default=0.0)
    host_1 = max((e for _, _, e in phases), default=0.0)
    long_gaps = [g for g in gaps if g[1] - g[0] >= MIN_GAP_S]
    inside = [(max(s, host_0), min(e, host_1)) for s, e in long_gaps
              if e > host_0 and s < host_1]
    idle_in = split_idle(inside, phases)
    idle_in["beyond_host_plane"] = sum(e - s for s, e in long_gaps) \
        - sum(e - s for s, e in inside)
    idle_in["between_ops"] = sum(e - s for s, e in gaps
                                 if e - s < MIN_GAP_S)
    return {
        "path": os.path.relpath(path), "scoped": scoped,
        "scope_s": scope_s, "op_s": op_s,
        "busy_s": busy,
        "span_s": (max(e for _, _, e in events)
                   - min(s for _, s, _ in events)) if events else 0.0,
        "sched": sched, "phase_s": phase_s, "idle_in_s": idle_in,
        "device_clock_lag_s": lag,
        "rounds": sum(1 for name, *_ in sched if name == SPAN_ROUND),
    }


def of_run(run: dict, kind: str) -> dict | None:
    """The reduced trace of the run that asks: None off ``kind``'s cells,
    without a trace, or where the program set none of its names (a commit
    from before they existed)."""
    if run["kind"] != kind or run.get("trace") is None:
        return None
    path = newest_xplane()
    reduced = load(path) if path else None
    if reduced is None:
        return None
    if not reduced["scoped"] and not reduced["sched"]:
        return None
    return reduced


def scope_share(run: dict, kind: str, scope: str,
                zero_is_true: bool = False) -> float | None:
    """Device time under ``scope`` as a percentage of the trace's busy
    time; raises, naming the scopes the trace does hold, when nothing
    carries ``scope`` in a trace that has others."""
    reduced = of_run(run, kind)
    if reduced is None or not reduced["scoped"]:
        return None
    seconds = under(reduced["scope_s"], scope)
    if not seconds and not zero_is_true:
        raise RuntimeError(
            f"no device op under scope {scope!r}; the trace holds "
            f"{sorted(innermost(reduced['scope_s']))}")
    return 100.0 * seconds / run["trace"]["busy_s"]


def idle_share(run: dict, phases: tuple[str, ...]) -> float | None:
    """Device-idle time inside the scheduler's ``phases`` as a percentage
    of the traced window (the serve cells')."""
    reduced = of_run(run, "serve")
    if reduced is None or not reduced["sched"]:
        return None
    missing = [p for p in phases if not reduced["phase_s"].get(p)]
    if missing:
        raise RuntimeError(
            f"no {SPAN_PREFIX}{missing[0]} span in the trace; it holds "
            f"{sorted({name for name, *_ in reduced['sched']})}")
    idle = sum(reduced["idle_in_s"].get(p, 0.0) for p in phases)
    return 100.0 * idle / run["trace"]["window_s"]


# -- the tables ---------------------------------------------------------------


def tables(reduced: dict, window_s: float | None = None) -> str:
    busy = reduced["busy_s"]
    window = max(window_s or 0.0, reduced["span_s"])
    lines = [f"trace {reduced['path']}: busy {busy:.4f} s of a window of "
             f"{window:.4f} s (first op to last {reduced['span_s']:.4f} s), "
             f"device idle {100 * (1 - busy / window):.2f} %", "",
             "device seconds by scope path (share of busy):"]

    def rows(d):
        return [f"  {s:9.4f} s {100 * s / busy:6.2f} %  {k}"
                for k, s in sorted(d.items(), key=lambda kv: -kv[1])]

    lines += rows(reduced["scope_s"])
    lines += ["", "by scope, wherever it stands in a path:"]
    lines += rows({name: under(reduced["scope_s"], name)
                   for name in DEVICE_SCOPES + (UNSCOPED,)
                   if under(reduced["scope_s"], name)})
    lines += ["", "largest unscoped ops:"]
    lines += rows(dict(sorted(
        ((name, s) for (path, name), s in reduced["op_s"].items()
         if not path), key=lambda kv: -kv[1])[:8]))
    if reduced["sched"]:
        idle = reduced["idle_in_s"]
        n = max(1, reduced["rounds"])
        lines += ["", f"scheduler thread, {reduced['rounds']} rounds "
                  "(seconds; ms a round; device idle inside, share of "
                  "the window; device times moved "
                  f"{1e3 * reduced['device_clock_lag_s']:.3f} ms later, the "
                  "least that lets no module start before its dispatch):"]
        for phase in SCHED_PHASES:
            s = reduced["phase_s"].get(phase, 0.0)
            lines.append(
                f"  {phase:9s}{s:9.4f} s {1e3 * s / n:8.3f} ms  idle "
                f"{idle.get(phase, 0.0):8.4f} s "
                f"{100 * idle.get(phase, 0.0) / window:6.2f} %")
        total_idle = window - busy
        rest = total_idle - idle.get("fetch", 0.0) - sum(
            idle.get(p, 0.0) for p in HOST_PHASES)
        lines += [
            f"  idle in fetch {100 * idle.get('fetch', 0.0) / window:.2f} "
            f"% + in the host's phases "
            f"{100 * sum(idle.get(p, 0.0) for p in HOST_PHASES) / window:.2f}"
            f" % + remainder {100 * rest / window:.2f} % = "
            f"{100 * total_idle / window:.2f} %",
            f"  remainder: in sched.idle "
            f"{100 * idle.get('idle', 0.0) / window:.2f} %, under no span "
            f"{100 * idle['uncovered'] / window:.2f} %, beyond the host "
            f"plane's first and last span "
            f"{100 * idle['beyond_host_plane'] / window:.2f} %, pauses under "
            f"{1e6 * MIN_GAP_S:.0f} us between ops "
            f"{100 * idle['between_ops'] / window:.2f} %, window beyond "
            f"the ops {100 * (window - reduced['span_s']) / window:.2f} %"]
    return "\n".join(lines)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv and argv[0].endswith(".pb") \
        else newest_xplane(argv[0] if argv else None)
    if path is None:
        print("no .xplane.pb found", file=sys.stderr)
        return 1
    reduced = load(path)
    if reduced is None:
        print(f"{path} holds no TPU plane", file=sys.stderr)
        return 1
    print(tables(reduced, float(argv[1]) if len(argv) > 1 else None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
