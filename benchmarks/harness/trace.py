"""A short profiler trace inside the measured window, and its reduction:
device busy time (the union of the device-op intervals), time per XLA
module and per op, and the idle gaps labelled by what the host was doing.

The program sets no named scopes yet, so the reduction goes by XLA module
and op names as they appear in the device plane. :func:`reduce` works on
plain tuples so that a hand-made event list can check it.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import threading
import time

import numpy as np

TRACE_SECONDS = 4.0
MIN_GAP_S = 20e-6      # shorter pauses are the device's own, between ops
LABELLED_GAPS = 300    # only the longest gaps are attributed


def module_key(name: str) -> str:
    """``jit_decode_step(1234)`` → ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def busy_union(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """(seconds covered by the union of ``(start, end)`` intervals, the
    gaps between the merged intervals as ``(start, end)``)."""
    busy, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def label_gaps(gaps: list[tuple[float, float]],
               host: list[tuple[str, float, float]]) -> list[tuple[str, float]]:
    """Each gap with the host event that overlaps it most (the shorter
    event where several cover it alike), or ``unattributed``."""
    if not host:
        return [("unattributed", e - s) for s, e in gaps]
    names = [h[0] for h in host]
    starts = np.array([h[1] for h in host])
    ends = np.array([h[2] for h in host])
    out = []
    for s, e in gaps:
        overlap = np.minimum(ends, e) - np.maximum(starts, s)
        best = overlap.max()
        if best <= 0:
            out.append(("unattributed", e - s))
            continue
        ties = np.flatnonzero(overlap >= best - 1e-9)
        pick = ties[np.argmin((ends - starts)[ties])]
        out.append((names[pick], e - s))
    return out


def reduce(ops: list[tuple[str, float, float]],
           modules: list[tuple[str, float, float]],
           host: list[tuple[str, float, float]], window_s: float) -> dict:
    """ops / modules / host are ``(name, start_s, end_s)`` on one clock.
    Returns busy_s, window_s, seconds per module and per op, counts per
    module, and the idle gaps summed by label."""
    busy, gaps = busy_union([(s, e) for _, s, e in ops])
    if ops:
        # The profiler keeps ops that were running as it started and as it
        # stopped: the window is at least the span they cover.
        window_s = max(window_s, max(e for _, _, e in ops)
                       - min(s for _, s, _ in ops))
    op_s: dict[str, float] = {}
    for name, s, e in ops:
        op_s[name] = op_s.get(name, 0.0) + (e - s)
    mod_s: dict[str, float] = {}
    mod_n: dict[str, int] = {}
    for name, s, e in modules:
        key = module_key(name)
        mod_s[key] = mod_s.get(key, 0.0) + (e - s)
        mod_n[key] = mod_n.get(key, 0) + 1
    gaps = sorted((g for g in gaps if g[1] - g[0] >= MIN_GAP_S),
                  key=lambda g: g[0] - g[1])
    by_label: dict[str, float] = {}
    for label, secs in label_gaps(gaps[:LABELLED_GAPS], host):
        by_label[label] = by_label.get(label, 0.0) + secs
    rest = sum(e - s for s, e in gaps[LABELLED_GAPS:])
    if rest:
        by_label["shorter_gaps"] = rest
    def top(d):
        # An op's name is its whole HLO line: the start of it says enough.
        return [[k[:120], v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": busy, "window_s": window_s, "module_s": mod_s,
            "module_n": mod_n, "op_s": op_s,
            "breakdown": {"device_ops": top(op_s),
                          "idle_gaps": top(by_label)}}


def load(trace_dir: str, chips: int, window_s: float) -> dict | None:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``; None where
    the trace holds no device plane (a CPU run)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    device_planes = [p for p in data.planes
                     if re.match(r"^/device:TPU:\d+$", p.name)][:chips]
    if not device_planes:
        return None

    def events(line):
        return [(ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events]

    host = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in events(line) if e[2] - e[1] >= 5e-6)
    reduced = []
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            raise RuntimeError(f"{plane.name} has no 'XLA Ops' line: "
                               f"{sorted(lines)}")
        reduced.append(reduce(events(lines["XLA Ops"]),
                              events(lines["XLA Modules"])
                              if "XLA Modules" in lines else [],
                              host, window_s))
    out = reduced[0]
    out["busy_s"] = sum(r["busy_s"] for r in reduced) / len(reduced)
    return out


class TraceWindow:
    """Starts a trace ``delay`` seconds into the measured window and stops
    it ``TRACE_SECONDS`` later, from a thread of its own so that the load
    generator never waits for the profiler."""

    def __init__(self, trace_dir: str, delay: float, seconds: float,
                 snapshot=None):
        self.dir = trace_dir
        self.window_s = 0.0
        # ``snapshot()`` is read as the trace starts and as it stops, so
        # that counters can be cut to the traced window.
        self._snapshot = snapshot
        self.marks: tuple | None = None
        self._delay = delay
        self._seconds = seconds
        self._thread = threading.Thread(target=self._run, daemon=True)
        self.error: Exception | None = None

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self._delay)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            first = self._snapshot() if self._snapshot else None
            t0 = time.perf_counter()
            time.sleep(self._seconds)
            self.window_s = time.perf_counter() - t0
            last = self._snapshot() if self._snapshot else None
            jax.profiler.stop_trace()
            self.marks = (first, last)
        except Exception as e:  # reported by the caller, on its thread
            self.error = e

    def finish(self) -> None:
        self._thread.join()
        if self.error is not None:
            raise self.error
