"""Shared benchmark machinery: dataset prep, training runs, CSV emission.

Every benchmark mirrors one table/figure of the paper (see benchmarks/run.py
for the index). Results are printed as CSV and dumped to results/bench/."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.graph import datasets
from repro.graph.events import EventStream, stack_batches
from repro.models import mdgnn
from repro.models.mdgnn import MDGNNConfig
from repro.optim import optimizers
from repro.train import loop, pipeline, scan

RESULTS_DIR = pathlib.Path(__file__).resolve().parent.parent / "results" / "bench"

VARIANTS = ("tgn", "jodie", "apan")


def bench_stream(n_events: int = 6000, seed: int = 0):
    """Scaled-down WIKI-like stream (the paper's primary dataset)."""
    spec = datasets.SyntheticSpec("wiki-bench", 400, 120, n_events, 8)
    return datasets.generate(spec, seed), spec


@dataclasses.dataclass
class RunResult:
    aps: list          # per-epoch AP
    losses: list
    epoch_seconds: list
    compile_seconds: float
    per_batch_aps: list
    # host->device step dispatches per epoch: K-1 for the per-batch loops,
    # ceil((K-1)/scan_chunk) for the scan-compiled engine — the denominator
    # of the wall-clock-per-dispatch column every fig reports
    dispatches_per_epoch: int = 0


def ms_per_dispatch(epoch_seconds: float, dispatches: int) -> float:
    """Wall-clock per host->device dispatch (ms) — reported alongside
    events/sec by every fig so dispatch-bound regimes are visible."""
    return epoch_seconds / max(dispatches, 1) * 1e3


def _copy_tree(tree):
    """Deep device copy — warm-up calls donate their opt/model state, so
    they must run on copies to keep the real training buffers alive."""
    return jax.tree.map(jnp.copy, tree)


def train_run(stream: EventStream, spec, *, variant="tgn", use_pres=False,
              batch_size=100, epochs=3, seed=0, beta=0.1,
              pres_scale="count", delta_mode="transition",
              use_smoothing=None, collect_per_batch=False,
              d_mem=32, n_layers=1, n_heads=2,
              use_kernels=False, dedup_embed=True, pipeline_depth=0,
              host_prefetch=False, scan_chunk=1,
              dst_range=None, obs_metrics=False) -> RunResult:
    cfg = MDGNNConfig(
        variant=variant, n_nodes=stream.num_nodes, d_edge=stream.feat_dim,
        d_mem=d_mem, d_msg=d_mem, d_time=16, d_embed=d_mem, n_neighbors=8,
        n_layers=n_layers, n_heads=n_heads, use_kernels=use_kernels,
        dedup_embed=dedup_embed,
        use_pres=use_pres, use_smoothing=use_smoothing, beta=beta,
        pres_scale=pres_scale, delta_mode=delta_mode,
        pipeline_depth=pipeline_depth, scan_chunk=scan_chunk,
        obs_metrics=obs_metrics)
    key = jax.random.PRNGKey(seed)
    params, _ = mdgnn.init_params(key, cfg)
    state = mdgnn.init_state(cfg)
    opt = optimizers.adamw(1e-3)
    opt_state = opt.init(params)
    # schedule routing: scan_chunk > 1 -> scan-compiled macro-batch engine;
    # otherwise the pipeline facade (depth 0 delegates to the sequential
    # loop, bit-exact). host_prefetch re-carves batches lazily each epoch
    # on a background thread instead of materialising the full list up
    # front (fig_pipeline measures exactly that difference)
    engine = scan.ScanEngine(cfg, opt) if scan_chunk > 1 else None
    step = None if engine else pipeline.make_train_step(cfg, opt)
    if host_prefetch:
        make_batches = lambda: stream.prefetch_batches(
            batch_size, depth=max(2, pipeline_depth))
        it = stream.iter_temporal_batches(batch_size)
        warm = (next(it), next(it))
    else:
        batches = stream.temporal_batches(batch_size)
        make_batches = lambda: batches
        warm = (batches[0], batches[1])
    # explicit dst_range lets spec-less sources (event stores, CSVs) run;
    # otherwise derived from the synthetic spec's bipartite band
    if dst_range is None:
        dst_range = (spec.n_users, spec.n_users + spec.n_items)
    n_steps = stream.num_batches(batch_size) - 1
    dispatches = -(-n_steps // scan_chunk) if scan_chunk > 1 else n_steps

    # compile (first step) timed separately so epoch_seconds is steady-state;
    # the steps donate their opt/model state, so warm-up runs on copies
    t0 = time.perf_counter()
    from repro.graph.negatives import NegativeDraw, sample_negatives
    if engine is not None:
        # a full-chunk macro when the stream has one (the tail-size compile
        # lands in epoch 0, which the figs drop as warm-up)
        warm_list = (batches[:scan_chunk + 1] if not host_prefetch
                     else list(warm))
        engine._macro_step(tuple(dst_range))(
            _copy_tree(params), _copy_tree(opt_state), _copy_tree(state),
            key, stack_batches(warm_list))
    elif pipeline_depth:
        pstate = pipeline.PipelineState.init(state["memory"])
        step(_copy_tree(params), _copy_tree(opt_state), _copy_tree(state),
             pstate, warm[0], warm[1],
             sample_negatives(key, warm[1], *dst_range))
    else:   # the sequential loop draws its negatives inside the step
        step(_copy_tree(params), _copy_tree(opt_state), _copy_tree(state),
             warm[0], warm[1], NegativeDraw.start(key, dst_range))
    compile_s = time.perf_counter() - t0

    aps, losses, secs, per_batch = [], [], [], []
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        if engine is not None:
            params, opt_state, state, res = engine.run_epoch(
                params, opt_state, state, make_batches(), sub, dst_range,
                collect_logits=collect_per_batch)
        else:
            params, opt_state, state, res = pipeline.run_epoch(
                params, opt_state, state, make_batches(), cfg, step, sub,
                dst_range, collect_logits=collect_per_batch)
        aps.append(res.ap)
        losses.append(res.loss)
        secs.append(res.seconds)
        if collect_per_batch:
            per_batch.extend(res.aps)
    return RunResult(aps, losses, secs, compile_s, per_batch,
                     dispatches_per_epoch=dispatches)


def run_metadata(cfg=None) -> dict:
    """Provenance stamped into every results JSON — delegates to
    obs.sink.run_metadata (one schema with the run-logs), which adds the
    git commit hash and, given a cfg, its sha256 digest: a committed
    results/bench/*.json row is thereby traceable to the exact revision
    AND model configuration that produced it."""
    from repro.obs import sink
    return sink.run_metadata(cfg)


def emit(name: str, rows: Sequence[dict], cfg=None):
    """Print CSV to stdout and persist JSON to results/bench/<name>.json
    as {"meta": run_metadata(cfg), "rows": [...]}."""
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    (RESULTS_DIR / f"{name}.json").write_text(
        json.dumps({"meta": run_metadata(cfg), "rows": list(rows)}, indent=2))
    if not rows:
        return
    cols = list(rows[0].keys())
    print(f"\n# --- {name} ---")
    print(",".join(cols))
    for r in rows:
        print(",".join(_fmt(r[c]) for c in cols))


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.4f}"
    return str(v)


def mean_std(xs):
    a = np.asarray(xs, np.float64)
    return float(a.mean()), float(a.std())
