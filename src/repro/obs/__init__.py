"""Unified telemetry layer (docs/OBSERVABILITY.md).

Three small modules with one contract — instrumentation must never add a
per-step host sync:

* `obs.metrics` — device-side per-step metric accumulation (the obs
  vector packed inside the jitted step, flushed to host ONCE per epoch),
  fixed log-spaced latency histograms, GMM tracker-health probes, and the
  shared route-overflow accumulator all three training engines use.
* `obs.trace`   — named-span stage tracing: `jax.named_scope` stages for
  the jitted pipeline (memory_update / embed / loss / apply /
  maintain_state), host spans on the profiler's clock for the non-jitted
  stages (the training loop's sampling, dispatch and pulls, the serve
  engine's padding, dispatch and pulls, prefetch, event-store windowing,
  checkpoint), host counters, and a bounded-window `jax.profiler` capture.
* `obs.sink`    — the JSONL run-log (one schema shared by train and
  serve), run manifests with git commit + config digest, and the
  canonicalisation helper the deterministic-log tests use.

`tools/inspect_run.py` renders a run-log into a terminal/markdown report.
"""
from repro.obs import metrics, sink, trace

__all__ = ["metrics", "sink", "trace"]
