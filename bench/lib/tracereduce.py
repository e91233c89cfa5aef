"""Reduction of a profiler trace to per-layer numbers.

`extract` reads the `.xplane.pb` that `jax.profiler` wrote and keeps two
lists of plain events: the device's operations, `{"device", "module",
"name", "start_ns", "dur_ns"}` from the "XLA Ops" line of each TPU plane
(`name` is the HLO instruction's name, `module` the program that ran it,
from the "XLA Modules" line; an op that spans others, as a `while` spans
its body, is dropped for them), and the host spans the benchmark annotated,
`{"name", "start_ns", "dur_ns"}` (names that start with "bench."). On a
TPU v5e the op events carry no name stack, so `hlo_scopes` reads it from
the compiled program's HLO text: `scopes[module][instruction] = op_name`.
Everything after that works on these plain lists, so the tests check it on
small recorded extracts (tests/data/).

* `window`: the traced stretch, the last host span of a given name.
* `busy_ns`: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices.
* `scope_ns`: device time of the operations whose name stack holds a
  `jax.named_scope` (forward `a/scope/b` or `jvp(scope)`, backward
  `transpose(jvp(scope))`).
* `module_ns`: device time of the operations of one program.
* `kernel_ns`: device time and call count of a Pallas kernel, whose
  custom call is named after the kernel's function (`_<kernel>_pallas`).
* `breakdown`: the operations that took most time, and the device's idle
  time summed by the innermost host span open in the middle of each gap.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PREFIX = "bench."
_INSTR = re.compile(r"^%?([^ =]+)")
_MODULE = re.compile(r"^([^(]+)")


def xplane_file(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "plugins",
                                          "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def extract(trace_dir) -> dict:
    """The trace's device operations and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_file(trace_dir))
    dev, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           _MODULE.match(ev.name).group(1))
                          for ev in (lines[MODULE_LINE].events
                                     if MODULE_LINE in lines else ()))
            starts = [s for s, _, _ in mods]
            for ev in (lines[OP_LINE].events if OP_LINE in lines else ()):
                s = ev.start_ns
                i = bisect.bisect_right(starts, s) - 1
                module = mods[i][2] if i >= 0 and s <= mods[i][1] else ""
                dev.append({"device": int(m.group(1)), "module": module,
                            "name": _INSTR.match(ev.name).group(1),
                            "start_ns": float(s),
                            "dur_ns": float(ev.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append({"name": ev.name,
                                     "start_ns": float(ev.start_ns),
                                     "dur_ns": float(ev.duration_ns)})
    return {"device": leaves(dev), "host": host, "scopes": {}}


def leaves(ops: list) -> list:
    """The operations that contain no other operation of their device: a
    `while` or `call` op spans the ops of its body, which the line lists
    too, so counting both would count that time twice."""
    out = []
    by_dev: dict = {}
    for o in ops:
        by_dev.setdefault(o["device"], []).append(o)
    for dev_ops in by_dev.values():
        dev_ops.sort(key=lambda o: (o["start_ns"], -o["dur_ns"]))
        for i, o in enumerate(dev_ops):
            nxt = dev_ops[i + 1] if i + 1 < len(dev_ops) else None
            if nxt is not None and nxt["start_ns"] < o["start_ns"] + o["dur_ns"] \
                    and nxt["start_ns"] + nxt["dur_ns"] <= o["start_ns"] + o["dur_ns"]:
                continue
            out.append(o)
    return out


_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([^ =]+) = .*?"
                       r'metadata=\{op_name="([^"]*)"')


def hlo_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name} from a compiled module's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def window(trace: dict, span: str) -> tuple:
    """(start_ns, end_ns) of the last host span named `span`."""
    spans = [h for h in trace["host"] if h["name"] == span]
    if not spans:
        raise ValueError(f"no host span {span!r} in the trace")
    s = max(spans, key=lambda h: h["start_ns"])
    return s["start_ns"], s["start_ns"] + s["dur_ns"]


def in_window(ops: list, win: tuple) -> list:
    lo, hi = win
    return [o for o in ops if o["start_ns"] < hi
            and o["start_ns"] + o["dur_ns"] > lo]


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def busy_ns(ops: list, win: tuple) -> float:
    """Union of operation intervals inside `win`, averaged over devices."""
    lo, hi = win
    per_dev: dict = {}
    for o in ops:
        s, e = max(o["start_ns"], lo), min(o["start_ns"] + o["dur_ns"], hi)
        if e > s:
            per_dev.setdefault(o["device"], []).append((s, e))
    if not per_dev:
        return 0.0
    return sum(_union(v) for v in per_dev.values()) / len(per_dev)


def scope_of(op: dict, scopes: dict) -> str:
    return scopes.get(op["module"], {}).get(op["name"], "")


def scope_ns(ops: list, scopes: dict, scope: str) -> float:
    """Device time of the operations under `jax.named_scope(scope)`,
    forward and backward, summed over devices."""
    pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
    return sum(o["dur_ns"] for o in ops if pat.search(scope_of(o, scopes)))


def module_ns(ops: list, module: str) -> float:
    """Device time of the operations of the programs named `module`."""
    return sum(o["dur_ns"] for o in ops if o["module"] == module)


def kernel_ns(ops: list, kernel: str) -> tuple:
    """(device time summed, number of calls) of a Pallas kernel."""
    pat = re.compile(r"^_?" + re.escape(kernel) + r"(_pallas)?(\.\d+)?$")
    ko = [o for o in ops if pat.match(o["name"])]
    return sum(o["dur_ns"] for o in ko), len(ko)


def _label(host: list, t: float) -> str:
    """The innermost benchmark span open at time t."""
    best, best_dur = "outside the benchmark's spans", float("inf")
    for h in host:
        if h["start_ns"] <= t <= h["start_ns"] + h["dur_ns"] \
                and h["dur_ns"] < best_dur:
            best, best_dur = h["name"], h["dur_ns"]
    return best


def breakdown(trace: dict, ops: list, win: tuple, top: int = 10) -> dict:
    """{"device_ops": [[name, seconds]], "idle_gaps": [[label, seconds]]}:
    device time by operation (its name stack where known, else module and
    instruction), longest first, and the gaps with no operation on the
    first device, summed by the host span open in their middle."""
    per: dict = {}
    for o in ops:
        key = scope_of(o, trace["scopes"]) or f"{o['module']}/{o['name']}"
        per[key] = per.get(key, 0.0) + o["dur_ns"]
    dev_ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    lo, hi = win
    first = min((o["device"] for o in ops), default=0)
    d0 = sorted((max(o["start_ns"], lo), min(o["start_ns"] + o["dur_ns"], hi))
                for o in ops if o["device"] == first)
    gaps, end = [], lo
    for s, e in d0:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if hi > end:
        gaps.append((end, hi))
    by_label: dict = {}
    for a, b in gaps:
        lab = _label(trace["host"], (a + b) / 2)
        n, tot = by_label.get(lab, (0, 0.0))
        by_label[lab] = (n + 1, tot + (b - a))
    idle = sorted(([f"{lab} ({n} gaps)", tot * 1e-9]
                   for lab, (n, tot) in by_label.items()),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in dev_ops],
            "idle_gaps": idle}
