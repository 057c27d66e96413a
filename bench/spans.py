"""The program's own spans (``seifer.*``) in a traced window, reduced for the
per-layer metrics that read them.

The serving engine opens its spans with ``jax.profiler.TraceAnnotation`` on
the host thread that calls ``step()`` (``repro.obs.profiler`` lists them).
They share the profiler's clock with the device's ops, so each idle
nanosecond of the chip can be named by what the host was doing then:

- the spans read are those on the thread that holds the ``seifer.step``
  events, that start inside the ``bench.window`` span;
- at each instant the *innermost* open span is the open one with the latest
  start (on one thread, spans nest);
- each idle nanosecond of chip 0 inside the window goes to the innermost
  open span; an idle stretch with no span open is left unattributed;
- a span's *self time* is its duration less the union of the named child
  spans inside it.

``bench/devtrace.py`` keeps only the harness's host spans, so
``program_spans`` reads the program's from the run's ``.xplane.pb`` itself:
the harness writes it under ``results/trace-<cell>-<seed>-<time>/`` beside
``bench/``, and the file read is the one whose ``bench.window`` is the
window of ``run.device``.  Each span is ``{"name", "start_ns", "dur_ns",
"thread", "args"}``: ``thread`` is the xplane line it ran on, ``args`` its
metadata (the event's stats).

``window_spans`` and the reductions built on it return ``None`` when the
trace holds no ``seifer.step``: a program without spans, or no trace.
"""

from __future__ import annotations

import glob
import heapq
from pathlib import Path

from bench.devtrace import WINDOW_SPAN

PREFIX = "seifer."
STEP = "seifer.step"
STAGE = "seifer.stage"
CODEC = "seifer.codec"
GC = "seifer.gc"
DISPATCH = (STAGE, CODEC)  # the stage executors and the link codecs
ENGINE = (STEP, "seifer.admit", "seifer.complete", "seifer.reconcile")

_READ: dict[tuple, list[dict]] = {}  # the five readers of a run parse it once


def results_dir(reader_file: str | Path) -> Path:
    """``results/`` of the checkout whose ``bench/metrics/`` holds
    ``reader_file``: where ``bench/run.py`` has the profiler write."""
    return Path(reader_file).resolve().parents[2] / "results"


def spans_from_xplane(path: str | Path) -> tuple[list[tuple[float, float]], list[dict]]:
    """The ``bench.window`` spans, as ``(start_ns, dur_ns)``, and the
    program's spans of one ``.xplane.pb``'s host planes."""
    from jax.profiler import ProfileData

    windows, program = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}:{line.name}"
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    windows.append((float(e.start_ns), float(e.duration_ns)))
                elif e.name.startswith(PREFIX):
                    program.append({"name": e.name, "start_ns": float(e.start_ns),
                                     "dur_ns": float(e.duration_ns),
                                     "thread": thread, "args": dict(e.stats)})
    return windows, program


def program_spans(run, results: Path) -> list[dict] | None:
    """The program spans of ``run``'s traced window, from the ``.xplane.pb``
    under ``results`` whose ``bench.window`` is ``run.device``'s; ``None``
    for an untraced run or when no such file is there."""
    if run is None or getattr(run, "device", None) is None:
        return None
    a, b = run.device.window_ns
    window = (a, b - a)
    key = (str(results), run.cell, run.seed, window)
    if key not in _READ:
        found = glob.glob(str(Path(results) / f"trace-{glob.escape(run.cell)}-{run.seed}-*"
                              / "plugins/profile/*/*.xplane.pb"))
        for path in sorted(found, key=lambda f: Path(f).stat().st_mtime, reverse=True):
            windows, program = spans_from_xplane(path)
            if window in windows:
                _READ[key] = program
                break
        else:
            return None
    return _READ[key]


def _end(s: dict) -> float:
    return s["start_ns"] + s["dur_ns"]


def window_spans(trace, program: list[dict] | None) -> list[dict] | None:
    """Of ``program``, the spans on the stepping thread that start inside
    ``trace``'s window, by start (an enclosing span before those it holds)."""
    if trace is None or not program:
        return None
    steps = [s for s in program if s["name"] == STEP]
    if not steps:
        return None
    threads: dict[str, int] = {}
    for s in steps:
        threads[s["thread"]] = threads.get(s["thread"], 0) + 1
    thread = max(threads, key=threads.get)
    a, b = trace.window_ns
    return sorted((s for s in program
                   if s["thread"] == thread and a <= s["start_ns"] < b),
                  key=lambda s: (s["start_ns"], -s["dur_ns"]))


def innermost(spans: list[dict]) -> list[tuple[float, float, dict]]:
    """``(start, end, span)`` stretches, in time order, over which ``span``
    is the innermost open one; stretches with no span open are left out."""
    bounds = sorted({s["start_ns"] for s in spans} | {_end(s) for s in spans})
    heap: list = []  # (-start, end, order, span): the latest start on top
    out, i = [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i]["start_ns"] <= a:
            s = spans[i]
            heapq.heappush(heap, (-s["start_ns"], _end(s), i, s))
            i += 1
        while heap and heap[0][1] <= a:  # closed
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][3]))
    return out


def idle_intervals(trace) -> list[tuple[float, float]]:
    """The stretches of the window in which chip 0 ran no op: the gaps
    ``DeviceTrace.idle_gaps`` ranks, computed apart so that the breakdown's
    code stays as it was."""
    a, b = trace.window_ns
    gaps, cur = [], a
    for s, t in trace.busy_intervals(0):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if b > cur:
        gaps.append((cur, b))
    return gaps


def idle_by_span(trace, program: list[dict] | None) -> dict[str, float] | None:
    """Idle nanoseconds of chip 0 in the window, by the name of the
    innermost open program span; unattributed idle is not in the dict."""
    spans = window_spans(trace, program)
    if spans is None:
        return None
    out: dict[str, float] = {}
    segs, gaps = innermost(spans), idle_intervals(trace)
    i = j = 0
    while i < len(segs) and j < len(gaps):
        (s, t, span), (u, v) = segs[i], gaps[j]
        lo, hi = max(s, u), min(t, v)
        if hi > lo:
            out[span["name"]] = out.get(span["name"], 0.0) + (hi - lo)
        if t <= v:
            i += 1
        else:
            j += 1
    return out


def idle_share(trace, program: list[dict] | None, names) -> float | None:
    """Share of the window, in percent, in which chip 0 was idle with one of
    ``names`` the innermost open program span."""
    by = idle_by_span(trace, program)
    if by is None:
        return None
    a, b = trace.window_ns
    return 100.0 * sum(by.get(n, 0.0) for n in names) / (b - a)


def _covered(lo: float, hi: float, spans: list[dict]) -> float:
    """Length of the union of ``spans`` clipped to ``[lo, hi]``."""
    total, cur = 0.0, lo
    for s in sorted(spans, key=lambda s: s["start_ns"]):
        a, b = max(s["start_ns"], cur), min(_end(s), hi)
        if b > a:
            total += b - a
            cur = b
    return total


def outermost(spans: list[dict], name: str) -> list[dict]:
    """The ``name`` spans that no other ``name`` span encloses."""
    out, end = [], float("-inf")
    for s in spans:  # by start, enclosing first
        if s["name"] != name:
            continue
        if s["start_ns"] >= end:
            out.append(s)
            end = _end(s)
        else:
            end = max(end, _end(s))
    return out


def self_ms(spans: list[dict], name: str, children) -> list[float]:
    """Self time, in ms, of each outermost ``name`` span: its duration less
    the union of the ``children`` spans inside it."""
    kids = [s for s in spans if s["name"] in children]
    out = []
    for s in outermost(spans, name):
        lo, hi = s["start_ns"], _end(s)
        inside = [k for k in kids if k["start_ns"] < hi and _end(k) > lo]
        out.append((s["dur_ns"] - _covered(lo, hi, inside)) * 1e-6)
    return out


def durations_ms(spans: list[dict], name: str) -> list[float]:
    """Durations in ms of the ``name`` spans."""
    return [s["dur_ns"] * 1e-6 for s in spans if s["name"] == name]


def mean(values) -> float | None:
    values = list(values)
    return sum(values) / len(values) if values else None
