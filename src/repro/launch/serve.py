"""Online MDGNN serving CLI + zoo decode driver (docs/SERVING.md).

Thin front-end over the serving subsystem (`repro.serve`): builds a
ServeEngine — from a training checkpoint when `--checkpoint` is given
(the launch/train.py `--checkpoint` bundle; model flags must match the
training run) — and drives it with the Poisson arrival-clock replay
harness over the stream's serving tail, reporting p50/p99 ingest/query
latency, events/sec and the online AP.

    PYTHONPATH=src python -m repro.launch.train --dataset wiki-small \
        --pres --checkpoint /tmp/wiki.ckpt
    PYTHONPATH=src python -m repro.launch.serve --dataset wiki-small \
        --pres --checkpoint /tmp/wiki.ckpt

Zoo serving: `--zoo <arch>` runs a reduced-config cached decode loop to
demonstrate the serve_step path end-to-end on CPU.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.graph import datasets
from repro.graph.datasets import SPECS
from repro.launch import compile_cache
from repro.models.mdgnn import (VARIANT_MEMORY_CELLS, MDGNNConfig,
                                init_params, init_state)
from repro.serve import MicroBatcher, ServeEngine, replay


def serve_mdgnn(args):
    if args.event_store:
        from repro.graph.store import EventStore
        est = EventStore.open(args.event_store)
        stream = est.stream()
        dst_range = est.dst_range()
    else:
        spec = SPECS[args.dataset]
        stream = datasets.get_dataset(args.dataset, args.seed)
        dst_range = (spec.n_users, spec.n_users + spec.n_items)
    cfg = MDGNNConfig(variant=args.model,
                      memory_cell=VARIANT_MEMORY_CELLS[args.model],
                      n_nodes=stream.num_nodes,
                      d_edge=stream.feat_dim, d_mem=args.d_mem,
                      d_msg=args.d_mem, d_embed=args.d_mem,
                      n_layers=args.n_layers, use_pres=args.pres,
                      use_kernels=args.use_kernels,
                      kernels_mode=args.kernels_mode,
                      event_store=args.event_store)
    _, serve_s = stream.train_serve_split(args.serve_frac)
    batcher = MicroBatcher(d_edge=stream.feat_dim)
    if args.checkpoint:
        engine = ServeEngine.from_checkpoint(args.checkpoint, cfg,
                                             batcher=batcher,
                                             item_range=dst_range)
        origin = f"checkpoint {args.checkpoint}"
    else:
        params, _ = init_params(jax.random.PRNGKey(args.seed), cfg)
        engine = ServeEngine(cfg, params, init_state(cfg), batcher=batcher,
                             item_range=dst_range)
        origin = "untrained params (pass --checkpoint for a trained model)"
    # telemetry (docs/OBSERVABILITY.md): same sink schema as train —
    # manifest first, one "serve" record with counters + full latency
    # histograms, then the span/kernel-dispatch epilogue
    runlog = None
    if args.metrics_out:
        from repro.obs import sink, trace as obs_trace
        obs_trace.enable()
        runlog = sink.RunLog(args.metrics_out, role="serve", cfg=cfg)
    tracer = None
    if args.trace_dir:
        from repro.obs import trace as obs_trace
        tracer = obs_trace.StepTraceCapture(args.trace_dir,
                                            n_steps=args.trace_steps)
        # each ingest dispatch is one traced "step" of the replay window
        engine.ingest = tracer.wrap(engine.ingest)
    # mean micro-batch = rate * tick; --batch-size sets it via the tick
    tick = args.batch_size / args.rate
    report = replay(engine, serve_s, dst_range, rate=args.rate, tick=tick,
                    query_batch=args.query_batch, seed=args.seed,
                    late_frac=args.late_frac, max_late=args.max_late,
                    max_events=args.max_events)
    if tracer is not None:
        tracer.stop()
    if runlog is not None:
        runlog.write(
            "serve", n_events=report.n_events, n_queries=report.n_queries,
            n_ticks=report.n_ticks, seconds=report.seconds,
            events_per_sec=report.events_per_sec,
            queries_per_sec=report.queries_per_sec,
            ingest_p50_ms=report.ingest_p50_ms,
            ingest_p99_ms=report.ingest_p99_ms,
            query_p50_ms=report.query_p50_ms,
            query_p99_ms=report.query_p99_ms,
            online_ap=report.online_ap, sim_seconds=report.sim_seconds,
            ingest_hist=report.ingest_hist, query_hist=report.query_hist,
            # post-warmup compile counter, keyed "kind size[ k]": any
            # nonzero count means a live request paid a jit trace
            post_warmup_traces={" ".join(map(str, k)): v for k, v in
                                report.post_warmup_traces.items()})
        runlog.close()
        print(f"[obs] run-log written to {args.metrics_out}")
    source = (f"store {args.event_store}" if args.event_store
              else args.dataset)
    print(f"[serve] {args.model}{'-PRES' if args.pres else ''} on "
          f"{source} ({origin})")
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        pol = kops.execution_policy()
        print(f"  kernels: backend={pol['backend']} mode={cfg.kernels_mode} "
              f"default={pol['default_mode']} "
              f"autotune_entries={pol['autotune_entries']}")
    print(f"  stream: {report.n_events} events over "
          f"{report.sim_seconds:.1f}s simulated arrivals "
          f"(rate={args.rate:.0f} ev/s, {report.n_ticks} ticks)")
    print(f"  ingest: p50={report.ingest_p50_ms:.2f}ms "
          f"p99={report.ingest_p99_ms:.2f}ms, "
          f"{report.events_per_sec:.0f} events/sec end-to-end")
    print(f"  query : p50={report.query_p50_ms:.2f}ms "
          f"p99={report.query_p99_ms:.2f}ms, "
          f"{report.queries_per_sec:.0f} queries/sec, "
          f"online AP={report.online_ap:.4f}")
    if args.topk:
        srcs = serve_s.src[:min(8, len(serve_s))]
        ts = serve_s.t[:min(8, len(serve_s))]
        scores, items = engine.recommend_topk(srcs, ts, args.topk)
        print(f"  topk  : k={args.topk} for {len(srcs)} sources, e.g. "
              f"src {int(srcs[0])} -> items {items[0].tolist()}")
    return report


def serve_zoo(arch: str, steps: int):
    from repro.archs.api import get_model
    from repro.configs import get_config

    cfg = get_config(arch).reduced()
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params, _ = model.init(key)
    b, cache_len = 2, 128
    state = model.init_decode_state(b, cache_len)
    if model.encode is not None:  # enc-dec (whisper): prefill encoder out
        feats = jax.random.normal(
            key, (b, cfg.enc_frames, cfg.d_model), cfg.dtype)
        state["enc_out"] = model.encode(params, feats)
    step = jax.jit(model.decode_step)
    tokens = jnp.zeros((b, 1), jnp.int32)
    t0 = time.perf_counter()
    for pos in range(steps):
        logits, state = step(params, state, tokens, jnp.int32(pos))
        tokens = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    jax.block_until_ready(tokens)
    dt = time.perf_counter() - t0
    print(f"[serve-zoo] {arch} (reduced): {steps} decode steps, "
          f"{steps * b / dt:.1f} tok/s on CPU")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="wiki-small", choices=list(SPECS))
    ap.add_argument("--event-store", default=None,
                    help="serve from an on-disk event store directory "
                         "instead of --dataset (tools/convert_events.py, "
                         "docs/DATA.md) — the replay tail stays memory-"
                         "mapped")
    ap.add_argument("--model", default="tgn", choices=["tgn", "jodie", "apan"])
    ap.add_argument("--pres", action="store_true")
    ap.add_argument("--n-layers", type=int, default=1,
                    help="embedding depth (hops for tgn)")
    ap.add_argument("--d-mem", type=int, default=100,
                    help="memory width — must match the checkpoint's run")
    ap.add_argument("--batch-size", type=int, default=200,
                    help="mean ingest micro-batch (sets the service tick "
                         "as batch-size/rate; the batcher buckets it)")
    ap.add_argument("--rate", type=float, default=5000.0,
                    help="Poisson arrival intensity, events/sec")
    ap.add_argument("--query-batch", type=int, default=32,
                    help="positive queries sampled per service tick")
    ap.add_argument("--serve-frac", type=float, default=0.3,
                    help="tail fraction of the stream replayed as live "
                         "traffic (0.15 = the chronological test split)")
    ap.add_argument("--late-frac", type=float, default=0.0,
                    help="fraction of events delivered out-of-order")
    ap.add_argument("--max-late", type=int, default=0,
                    help="bound (positions) on out-of-order delivery")
    ap.add_argument("--max-events", type=int, default=None,
                    help="cap on replayed events (CI smoke)")
    ap.add_argument("--topk", type=int, default=0,
                    help="also demo recommend_topk with this k")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route ingest folding and topk scoring through "
                         "the registered Pallas kernels (docs/KERNELS.md)")
    ap.add_argument("--kernels-mode", default="auto",
                    choices=["auto", "compiled", "interpret", "oracle"],
                    help="kernel execution mode (docs/KERNELS.md §Execution "
                         "policy): auto resolves per backend + autotune "
                         "cache; the others pin every dispatch")
    ap.add_argument("--checkpoint", default=None,
                    help="training checkpoint to serve "
                         "(launch/train.py --checkpoint bundle)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a JSONL run-log (docs/OBSERVABILITY.md): "
                         "manifest + a serve record with counters, full "
                         "log-bucketed ingest/query latency histograms, "
                         "post-warmup trace counts, host spans and the "
                         "kernel-dispatch table; render with "
                         "tools/inspect_run.py")
    ap.add_argument("--trace-dir", default=None,
                    help="capture a jax.profiler trace of the replay "
                         "(bounded to the first --trace-steps ticks)")
    ap.add_argument("--trace-steps", type=int, default=8,
                    help="tick window length for --trace-dir")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--zoo", default=None, help="serve a zoo arch instead")
    ap.add_argument("--steps", type=int, default=16)
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.zoo:
        serve_zoo(args.zoo, args.steps)
    else:
        serve_mdgnn(args)


if __name__ == "__main__":
    main()
