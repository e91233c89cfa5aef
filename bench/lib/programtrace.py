"""The program's own host spans in a profiler trace, and the device's idle
time inside them.

While its spans are enabled (`repro.obs.trace.enable()`), the program
opens a `jax.profiler.TraceAnnotation` named "repro." + the span's name
around each host stage: the training loop's `train.sample`,
`train.dispatch`, `train.pull` and `train.epoch_end`, the serve engine's
`serve.pad`, `serve.dispatch` and `serve.pull`. The span's arguments (a
step index; a request kind and a chunk index) are the event's stats. `extract` reads these events from the trace's host planes, as
`tracereduce.extract` reads the benchmark's own; everything after works
on plain lists, so the tests check it on hand-built extracts.

* `gaps`: the intervals of a window in which no operation ran on the
  first device.
* `inside`: intervals clipped to the union of some spans.
* `idle_ns_in`: the device-idle time of a window that falls inside the
  spans of one name (or of any name).
"""
from __future__ import annotations

import bisect

from bench.lib import tracereduce

PREFIX = "repro."


def extract(trace_dir) -> list:
    """The program's host spans, `{"name", "start_ns", "dur_ns", "args"}`,
    in order of their start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(tracereduce.xplane_file(trace_dir))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append({"name": ev.name,
                                "start_ns": float(ev.start_ns),
                                "dur_ns": float(ev.duration_ns),
                                "args": dict(ev.stats)})
    return sorted(out, key=lambda s: s["start_ns"])


def gaps(ops: list, win: tuple) -> list:
    """(start, end) of each stretch of `win` in which no operation ran on
    the first device, as `tracereduce.breakdown` finds them."""
    lo, hi = win
    first = min((o["device"] for o in ops), default=0)
    busy = sorted((max(o["start_ns"], lo), min(o["start_ns"] + o["dur_ns"], hi))
                  for o in ops if o["device"] == first)
    out, end = [], lo
    for s, e in busy:
        if s > end:
            out.append((end, s))
        end = max(end, e)
    if hi > end:
        out.append((end, hi))
    return out


def _merged(spans: list) -> list:
    out: list = []
    for s, e in sorted((h["start_ns"], h["start_ns"] + h["dur_ns"])
                       for h in spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def inside(intervals: list, spans: list) -> list:
    """The parts of `intervals` that fall inside the union of `spans`."""
    union = _merged(spans)
    ends = [e for _, e in union]
    out = []
    for a, b in intervals:
        j = bisect.bisect_right(ends, a)
        while j < len(union) and union[j][0] < b:
            s, e = union[j]
            out.append((max(a, s), min(b, e)))
            j += 1
    return out


def total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def idle_ns_in(ops: list, win: tuple, spans: list, name=None) -> float:
    """Device-idle time of the first device, inside `win`, that falls
    inside the spans named `name` (every span given, where None)."""
    chosen = [s for s in spans if name is None or s["name"] == name]
    return total(inside(gaps(ops, win), chosen))
