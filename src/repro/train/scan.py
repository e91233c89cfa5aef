"""Scan-compiled macro-batch training (docs/SCAN.md).

The sequential loop (repro.train.loop) dispatches one jitted step per
temporal batch from Python: per-step dispatch latency and a host transfer
of the step's logits. PRES exists to raise the effective temporal batch
size, so in the small-batch regimes the paper sweeps (Fig. 3/5) that fixed
per-batch tax dominates the actual compute. This module compiles the
lag-one recurrence itself:

* T consecutive temporal batches are stacked into one (T+1, b, ...)
  *macro-batch* (`events.stack_batches` / `events.iter_macro_batches`,
  overlapping by one batch because batch i-1 updates the memory that
  predicts batch i);
* ONE jitted call runs the existing train-step body
  (`loop.make_step_body` — kernel routing, PRES fusion and all) under
  `jax.lax.scan`, carry = (params, opt_state, full model state, PRNG key);
* negative sampling happens INSIDE the step (`split_and_sample`, driven
  by the carried key — the helper the sequential step's in-step draw uses
  too, so the negatives are bit-identical to the sequential loop's);
* per-step metrics come back stacked on device: one dispatch and one host
  transfer per T batches instead of per batch;
* the carry's big buffers (memory table, neighbour ring buffers, PRES
  trackers, APAN mailbox, optimizer state) are DONATED, so XLA aliases
  the (N, D) tables in place across the whole macro-batch.

`cfg.scan_chunk = 1` delegates to the sequential loop verbatim —
bit-exact with the historical path (pinned in tests/test_scan.py).
`scan_chunk` and `pipeline_depth` are mutually exclusive for now: the
pipelined step threads an extra PipelineState and its own facade; fusing
the two schedules is future work (docs/SCAN.md §Pipeline interaction).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.events import EventBatch, iter_macro_batches
from repro.graph.negatives import split_and_sample
from repro.models.mdgnn import MDGNNConfig
from repro.obs import metrics as obs_metrics
from repro.train import loop as loop_lib
from repro.utils import metrics as metrics_lib


def check_schedule(cfg: MDGNNConfig) -> None:
    """scan_chunk and pipeline_depth are mutually exclusive (for now)."""
    if cfg.scan_chunk < 1:
        raise ValueError(f"scan_chunk must be >= 1, got {cfg.scan_chunk}")
    if cfg.scan_chunk > 1 and cfg.pipeline_depth >= 1:
        raise ValueError(
            "scan_chunk > 1 and pipeline_depth >= 1 are mutually exclusive: "
            "the scan-compiled engine runs the strictly sequential lag-one "
            "body device-resident, while the pipelined schedule threads a "
            "PipelineState snapshot through every step. Pick one — "
            "scan_chunk for dispatch-bound (small-batch) regimes, "
            "pipeline_depth for memory/embed overlap (docs/SCAN.md "
            "§Pipeline interaction)")


def make_macro_step(cfg: MDGNNConfig, opt, dst_range, gru_fn=None):
    """Jitted scan-compiled macro step.

    Signature: (params, opt_state, state, key, macro) ->
               (params, opt_state, state, key, metrics)
    where `macro` is a stacked (T+1, b, ...) EventBatch and `metrics` holds
    the T per-step values stacked on device ({loss (T,), logit_p (T, b),
    logit_n (T, b), ...}). One compile per distinct T (the epoch tail runs
    a shorter macro). opt_state and state are DONATED — reuse only the
    returned carry."""
    check_schedule(cfg)
    body = loop_lib.make_step_body(cfg, opt, gru_fn=gru_fn)
    dst_lo, dst_hi = dst_range

    def macro_step(params, opt_state, state, key, macro: EventBatch):
        prevs = jax.tree.map(lambda x: x[:-1], macro)
        poss = jax.tree.map(lambda x: x[1:], macro)

        def step(carry, xs):
            params, opt_state, state, key = carry
            prev_batch, pos = xs
            neg, key = split_and_sample(key, pos, dst_lo, dst_hi)
            params, opt_state, state, m = body(params, opt_state, state,
                                               prev_batch, pos, neg)
            return (params, opt_state, state, key), m

        (params, opt_state, state, key), metrics = jax.lax.scan(
            step, (params, opt_state, state, key), (prevs, poss))
        return params, opt_state, state, key, metrics

    # sharded runs: replicate the host-produced key/macro onto the mesh
    # before the jitted call (the carries are already mesh-placed)
    return loop_lib._replicating_inputs(
        cfg, jax.jit(macro_step, donate_argnums=(1, 2)), n_carry=3)


class ScanEngine:
    """Epoch driver for scan-compiled macro-batch training.

    Owns the per-T compiled macro steps (an epoch of K batches runs
    floor((K-1)/T) full macros plus one tail macro — two compilations,
    cached across epochs) and the chunk=1 delegation to the sequential
    loop. Use exactly like loop.run_epoch:

        engine = ScanEngine(cfg, opt)
        params, opt_state, state, res = engine.run_epoch(
            params, opt_state, state, batches, key, dst_range)
    """

    def __init__(self, cfg: MDGNNConfig, opt, gru_fn=None, step_hook=None):
        check_schedule(cfg)
        self.cfg = cfg
        self.opt = opt
        self.gru_fn = gru_fn
        # optional wrapper applied around each compiled step callable —
        # the launch CLI's bounded jax.profiler capture
        # (obs.trace.StepTraceCapture.wrap) hooks in here
        self.step_hook = step_hook
        # per-instance cache (NOT lru_cache on the method, which would pin
        # every engine + its executables in a class-level cache for the
        # process lifetime): one jitted callable per dst_range serves every
        # T — jit re-traces per (T+1, b) macro shape internally
        self._steps: dict = {}

    def _macro_step(self, dst_range):
        if dst_range not in self._steps:
            step = make_macro_step(self.cfg, self.opt, dst_range,
                                   gru_fn=self.gru_fn)
            if self.step_hook is not None:
                step = self.step_hook(step)
            self._steps[dst_range] = step
        return self._steps[dst_range]

    @functools.cached_property
    def _seq_step(self):
        step = loop_lib.make_train_step(self.cfg, self.opt,
                                        gru_fn=self.gru_fn)
        return step if self.step_hook is None else self.step_hook(step)

    def run_epoch(self, params, opt_state, state, batches, key, dst_range,
                  collect_logits=False):
        """One epoch over `batches` (list or lazy/prefetching iterator)."""
        if self.cfg.scan_chunk == 1:      # bit-exact sequential delegation
            return loop_lib.run_epoch(params, opt_state, state, batches,
                                      self.cfg, self._seq_step, key,
                                      dst_range,
                                      collect_logits=collect_logits)
        t0 = time.perf_counter()
        step = self._macro_step(tuple(dst_range))
        losses, pos_all, neg_all = [], [], []
        obs = obs_metrics.EpochObs()
        it = iter_macro_batches(batches, self.cfg.scan_chunk)
        try:
            for macro in it:
                params, opt_state, state, key, m = step(
                    params, opt_state, state, key, macro)
                losses.append(m["loss"])              # (T,) device
                pos_all.append(np.asarray(m["logit_p"]))   # (T, b)
                neg_all.append(np.asarray(m["logit_n"]))
                obs.step(m)          # stacked (T,) / (T, F) device chunks
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()
        losses = np.concatenate([np.asarray(x) for x in losses])
        route_overflow, obs_out = obs.finish()
        pos_rows = [p for chunk in pos_all for p in chunk]
        neg_rows = [n for chunk in neg_all for n in chunk]
        ap = metrics_lib.average_precision(np.concatenate(pos_rows),
                                           np.concatenate(neg_rows))
        aps = [metrics_lib.average_precision(p, n)
               for p, n in zip(pos_rows, neg_rows)] if collect_logits else []
        dt = time.perf_counter() - t0
        return params, opt_state, state, loop_lib.EpochResult(
            ap, float(np.mean(losses)), dt, aps,
            route_overflow=route_overflow, obs=obs_out)
