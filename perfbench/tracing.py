"""Profiler traces -> numbers.  Kept with the benchmark so every PR reduces
a trace the same way; checked by ``tests/test_tracing.py`` on a small trace
recorded on the CPU.

A trace is reduced to intervals on one clock (nanoseconds):

* device operations: events on the ``XLA Ops`` lines of the device planes;
* device programs: events on the ``XLA Modules`` lines (named after the
  jitted function, e.g. ``jit_run(...)``);
* host spans: the events of the host planes, which hold the benchmark's own
  ``TraceAnnotation``s and, with the Python tracer on, the program's
  functions.  There are millions of them, so they are read lazily: once to
  find the window span, once for the spans that overlap the gaps asked for.

``busy`` is the union of the device operations inside the window, the idle
share is one minus busy over the window, and each idle gap is attributed to
the innermost host span that covers at least half of it.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Any

WINDOW_SPAN = "bench.window"
#: the window span is looked for among host events at least this long
_MIN_WINDOW_NS = 10_000_000


@dataclass
class Trace:
    ops: list = field(default_factory=list)      # (start, end, name, device)
    modules: list = field(default_factory=list)  # (start, end, name)
    host_lines: list = field(default_factory=list)   # (thread name, line)
    window: tuple | None = None
    devices: int = 0
    profile: Any = None      # keeps the lines' storage alive


def device_lines(plane: str, line: str):
    """Default classification of a trace line: device planes' ``XLA Ops``
    and ``XLA Modules`` lines, and every line of the host planes."""
    if plane.startswith("/device:"):
        return {"XLA Ops": "op", "XLA Modules": "module"}.get(line)
    return "host"


def load(path_or_dir: str, classify=device_lines,
         window_span: str = WINDOW_SPAN) -> Trace:
    """Read the newest ``.xplane.pb`` under a directory (or one file);
    ``classify(plane, line)`` says which lines hold device operations
    (``"op"``), device programs (``"module"``) or host spans (``"host"``)."""
    from jax.profiler import ProfileData

    path = path_or_dir
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(files, key=os.path.getmtime)
    tr = Trace(profile=ProfileData.from_file(path))
    for plane in tr.profile.planes:
        dev = tr.devices
        kinds = set()
        for line in plane.lines:
            kind = classify(plane.name, line.name)
            kinds.add(kind)
            if kind == "host":
                tr.host_lines.append((line.name, line))
                for ev in line.events:
                    if (tr.window is None and ev.duration_ns >= _MIN_WINDOW_NS
                            and ev.name == window_span):
                        s = int(ev.start_ns)
                        tr.window = (s, s + int(ev.duration_ns))
            elif kind == "op":
                for ev in line.events:
                    s = int(ev.start_ns)
                    tr.ops.append((s, s + int(ev.duration_ns), ev.name, dev))
            elif kind == "module":
                for ev in line.events:
                    s = int(ev.start_ns)
                    tr.modules.append((s, s + int(ev.duration_ns), ev.name))
        tr.devices += int("op" in kinds)
    return tr


def window(tr: Trace) -> tuple:
    if tr.window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return tr.window


def union(intervals, lo: int, hi: int) -> list:
    """Merged ``(start, end)`` of the intervals, clipped to ``[lo, hi]``."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(tr: Trace, lo: int, hi: int) -> float:
    """Time some operation ran, per device, averaged over the devices."""
    per = {}
    for iv in tr.ops:
        per.setdefault(iv[3], []).append(iv)
    if not per:
        return 0.0
    return sum(sum(e - s for s, e in union(ivs, lo, hi))
               for ivs in per.values()) / len(per)


def named_ns(intervals, pattern: str, lo: int, hi: int) -> tuple:
    """Summed time (clipped to the window) and count of the events whose
    name matches ``pattern``."""
    rx = re.compile(pattern)
    inside = [iv for iv in intervals
              if iv[1] > lo and iv[0] < hi and rx.search(iv[2])]
    return sum(min(e, hi) - max(s, lo) for s, e, *_ in inside), len(inside)


def top_ops(tr: Trace, lo: int, hi: int, k: int = 10, width: int = 96) -> list:
    """The ``k`` device operations with the most time in the window, as
    ``[name, seconds]``; an HLO op's name is its instruction text, cut to
    ``width`` characters."""
    tot: dict = {}
    for s, e, n, _dev in tr.ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            tot[n] = tot.get(n, 0) + (e - s)
    return sorted(([n[:width], t * 1e-9] for n, t in tot.items()),
                  key=lambda x: -x[1])[:k]


def gaps(tr: Trace, lo: int, hi: int) -> list:
    """``(start, end)`` intervals inside the window in which no device ran
    an operation."""
    out, at = [], lo
    for s, e in union(tr.ops, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def host_spans(tr: Trace, intervals: list, skip=(WINDOW_SPAN,)) -> list:
    """``(start, end, name, thread)`` of the host events that overlap any
    of the intervals."""
    if not intervals:
        return []
    lo = min(s for s, _e in intervals)
    hi = max(e for _s, e in intervals)
    out = []
    for thread, line in tr.host_lines:
        for ev in line.events:
            s = int(ev.start_ns)
            e = s + int(ev.duration_ns)
            if e <= lo or s >= hi:
                continue
            if any(e > gs and s < ge for gs, ge in intervals):
                name = ev.name
                if name not in skip:
                    out.append((s, e, name, thread))
    return out


def attribute(spans: list, gap: tuple) -> str:
    """What the host was doing in a gap: the innermost span covering at
    least half of it (``thread: name``), else ``idle``."""
    gs, ge = gap
    best = None
    for s, e, n, th in spans:
        cover = min(e, ge) - max(s, gs)
        if cover * 2 >= ge - gs and (best is None or e - s < best[0]):
            best = (e - s, f"{th}: {n}")
    return best[1] if best else "idle"


def idle_gaps(tr: Trace, lo: int, hi: int, k: int = 10) -> list:
    """The ``k`` longest idle gaps as ``[what the host did, seconds]``."""
    gs = sorted(gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:k]
    spans = host_spans(tr, gs)
    return [[attribute(spans, g), (g[1] - g[0]) * 1e-9] for g in gs]
