"""Named-span stage tracing (docs/OBSERVABILITY.md §Spans).

Two kinds of spans, matching the two halves of a training/serving step:

* `stage(name)` — a `jax.named_scope` for the jitted pipeline stages
  (`memory_update -> embed -> loss -> apply -> maintain_state`). Names
  land in the HLO op names, so a profiler trace read against the
  compiled program attributes device time to them; at runtime the
  annotation is free, so stages are always on.
* `span(name, **args)` — a host span for the not-jitted stages: the
  training loop's sampling, step dispatch and logits pull, the serve
  engine's padding, dispatch and answer pulls, prefetch waits, event-store
  windowing, checkpoint IO. Recording is gated by `enable()`. Disabled
  (the default) `span` returns one shared no-op context manager after a
  single global read: no clock read, no profiler call. Enabled, it opens
  a `jax.profiler.TraceAnnotation` named "repro." + name, carrying
  `args` (a step, chunk or kind), so a captured profiler trace holds each
  host stage on the device trace's own clock; it also appends
  {name, t0, dur_s, args} to the in-memory list `drain()` returns.

`count(name, n)` adds to a named host counter under the same switch
(`drain_counts()` returns and clears the totals): the serve engine counts
the rows it was asked for and the rows it sent after padding.

`StepTraceCapture` wraps a jitted step callable and captures a real
`jax.profiler` trace for a bounded step window (`--trace-dir` /
`--trace-steps` in the launch CLIs), each step bracketed by a
`StepTraceAnnotation`.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time

import jax

# every host span's profiler annotation starts with this, so a trace
# reader can tell the program's spans from anyone else's
PREFIX = "repro."

_lock = threading.Lock()
_enabled = False
_spans: list[dict] = []
_counts: dict[str, int] = {}
_t0 = 0.0


def stage(name: str):
    """Named scope for a jitted pipeline stage (free at runtime)."""
    return jax.named_scope(name)


def enable() -> None:
    """Start recording host spans and counts (span timestamps relative to
    this call)."""
    global _enabled, _t0
    with _lock:
        _spans.clear()
        _counts.clear()
        _t0 = time.perf_counter()
        _enabled = True


def disable() -> None:
    global _enabled
    with _lock:
        _enabled = False


def enabled() -> bool:
    return _enabled


def drain() -> list[dict]:
    """Return and clear the recorded spans ([{name, t0, dur_s, args}, ...];
    `args` only where the span was given some)."""
    with _lock:
        out, _spans[:] = list(_spans), []
    return out


def count(name: str, n: int = 1) -> None:
    """Add `n` to the host counter `name`. No-op unless `enable()`d."""
    if not _enabled:
        return
    with _lock:
        _counts[name] = _counts.get(name, 0) + int(n)


def drain_counts() -> dict[str, int]:
    """Return and clear the counter totals ({name: total})."""
    with _lock:
        out = dict(_counts)
        _counts.clear()
    return out


class _Span:
    """An enabled host span: the profiler annotation is opened first and
    closed last, so it brackets the timed stretch."""

    __slots__ = ("name", "args", "_ann", "_start")

    def __init__(self, name: str, args: dict):
        self.name, self.args = name, args

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                 **self.args)
        self._ann.__enter__()
        self._start = time.perf_counter()

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = {"name": self.name, "t0": self._start - _t0,
               "dur_s": end - self._start}
        if self.args:
            rec["args"] = self.args
        with _lock:
            if _enabled:
                _spans.append(rec)
        return False


# the one object every disabled span returns
_NOOP = contextlib.nullcontext()


def span(name: str, **args):
    """Host span (see the module docstring). Safe from any thread: the
    prefetch producer records through the same collector as the main
    thread."""
    if not _enabled:
        return _NOOP
    return _Span(name, args)


def span_summary(spans: list[dict]) -> dict:
    """Aggregate drained spans per name: {name: {count, total_s, max_s}}."""
    out: dict = {}
    for s in spans:
        agg = out.setdefault(s["name"], {"count": 0, "total_s": 0.0,
                                         "max_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += s["dur_s"]
        agg["max_s"] = max(agg["max_s"], s["dur_s"])
    return out


class StepTraceCapture:
    """Capture a `jax.profiler` trace for the first `n_steps` invocations
    of a wrapped step callable.

    trace = StepTraceCapture("/tmp/trace", n_steps=8)
    step = trace.wrap(step)          # per-call StepTraceAnnotation
    ... run the epoch ...
    trace.stop()                     # idempotent; also stops at step n

    The window is bounded so `--trace-dir` on a long run captures a
    steady-state slice instead of gigabytes of events; the trace starts at
    the first wrapped call, which on warm-compiled runs is already past
    the compile."""

    def __init__(self, trace_dir: str, n_steps: int = 8):
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        self.trace_dir = trace_dir
        self.n_steps = n_steps
        self._calls = 0
        self._active = False

    def wrap(self, fn):
        @functools.wraps(fn)
        def wrapped(*args, **kw):
            i = self._calls
            self._calls += 1
            if i == 0:
                jax.profiler.start_trace(self.trace_dir)
                self._active = True
            if not self._active:
                return fn(*args, **kw)
            with jax.profiler.StepTraceAnnotation("step", step_num=i):
                out = fn(*args, **kw)
            if self._calls >= self.n_steps:
                self.stop(block_on=out)
            return out

        return wrapped

    def stop(self, block_on=None) -> None:
        """Stop the capture (no-op if never started / already stopped).
        `block_on` is synced first so the traced window contains the
        device work the last wrapped dispatch enqueued."""
        if self._active:
            if block_on is not None:
                jax.block_until_ready(block_on)
            jax.profiler.stop_trace()
            self._active = False
