"""End-to-end MDGNN training driver (the paper's experiment loop).

Example:
    PYTHONPATH=src python -m repro.launch.train \
        --dataset wiki-small --model tgn --pres --batch-size 1000 \
        --epochs 10 --beta 0.1
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.graph import datasets
from repro.graph.datasets import SPECS
from repro.launch import compile_cache
from repro.models.mdgnn import (VARIANT_MEMORY_CELLS, MDGNNConfig,
                                init_params, init_state)
from repro.optim import adamw
from repro.train import loop, pipeline, scan
from repro.checkpoint import save_checkpoint


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="wiki-small", choices=list(SPECS))
    ap.add_argument("--csv", default=None, help="path to a real JODIE csv")
    ap.add_argument("--event-store", default=None,
                    help="path to an on-disk event store directory "
                         "(tools/convert_events.py, docs/DATA.md): trains "
                         "from windowed memmap slices with bounded RSS, "
                         "bit-identical to the in-RAM path")
    ap.add_argument("--model", default="tgn", choices=["tgn", "jodie", "apan"])
    ap.add_argument("--pres", action="store_true")
    ap.add_argument("--beta", type=float, default=0.1)
    ap.add_argument("--delta-mode", default="transition",
                    choices=["innovation", "transition"])
    ap.add_argument("--pres-scale", default="count", choices=["count", "time"],
                    help="Eq. 7 extrapolation scale (count = our adaptation, "
                         "time = paper-literal)")
    ap.add_argument("--batch-size", type=int, default=500)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--d-mem", type=int, default=100)
    ap.add_argument("--n-layers", type=int, default=1,
                    help="embedding depth: hops of temporal attention (tgn) "
                         "or stacked mailbox layers (apan); jodie has one")
    ap.add_argument("--n-heads", type=int, default=2,
                    help="attention heads in the embedding stack")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-dedup-embed", action="store_true",
                    help="disable unique-frontier compaction in the "
                         "embedding stack and run the seed L-hop expansion "
                         "(M*K^d rows per hop) instead of the deduplicated "
                         "unique tables (docs/DESIGN.md §Embedding stack)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route the full memory-maintenance step (the "
                         "fused memory cell + PRES filter kernel under "
                         "--pres, gru_cell otherwise) and the embedding "
                         "attention through "
                         "the registered Pallas kernels (docs/KERNELS.md)")
    ap.add_argument("--kernels-mode", default="auto",
                    choices=["auto", "compiled", "interpret", "oracle"],
                    help="kernel execution mode (docs/KERNELS.md §Execution "
                         "policy): auto resolves per backend + autotune "
                         "cache; the others pin every dispatch")
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="staleness-aware pipelined schedule: the embedding "
                         "stage reads a memory snapshot at most K batch-"
                         "writes stale, PRES-predict-filled (docs/PIPELINE.md)"
                         "; 0 = strictly sequential Alg. 1/2")
    ap.add_argument("--scan-chunk", type=int, default=1,
                    help="scan-compiled macro-batch training (docs/SCAN.md): "
                         "T consecutive lag-one steps run under ONE "
                         "jax.lax.scan dispatch with in-step negative "
                         "sampling and donated state; 1 = the sequential "
                         "per-batch loop (bit-exact). Mutually exclusive "
                         "with --pipeline-depth >= 1")
    ap.add_argument("--n-shards", type=int, default=1,
                    help="memory-parallel shards (docs/DISTRIBUTED.md): "
                         "partitions every node-indexed table over a "
                         "jax.sharding.Mesh by node_id %% n_shards with one "
                         "all_to_all routing exchange per step; needs "
                         ">= n_shards jax devices (emulate on CPU with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--shard-budget", type=int, default=None,
                    help="static per-(sender, owner) routing-lane budget; "
                         "default derives the overflow-free bound, smaller "
                         "values trade dropped updates (counted in "
                         "route_overflow) for smaller exchanges")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--metrics-out", default=None,
                    help="write a JSONL run-log (docs/OBSERVABILITY.md): "
                         "manifest + per-epoch records with the device-"
                         "accumulated telemetry series (loss, Eq. 10 "
                         "coherence cosine, PRES prediction-error stats, "
                         "staleness, route_overflow), GMM tracker health, "
                         "host spans and the kernel-dispatch table; render "
                         "with tools/inspect_run.py")
    ap.add_argument("--trace-dir", default=None,
                    help="capture a jax.profiler trace of the first "
                         "--trace-steps train-step dispatches into this "
                         "directory (bounded window; docs/OBSERVABILITY.md "
                         "§Profiler capture)")
    ap.add_argument("--trace-steps", type=int, default=8,
                    help="step-dispatch window length for --trace-dir")
    args = ap.parse_args(argv)
    compile_cache.enable()

    streamed = args.event_store is not None
    if streamed:
        from repro.graph.store import EventStore
        est = EventStore.open(args.event_store)
        stream = est.stream()
        spec = None
        dst_range = est.dst_range()
    elif args.csv:
        from repro.graph.events import load_jodie_csv
        stream = load_jodie_csv(args.csv)
        spec = None
        dst_range = (0, stream.num_nodes)
    else:
        spec = SPECS[args.dataset]
        stream = datasets.get_dataset(args.dataset, args.seed)
        dst_range = (spec.n_users, spec.n_users + spec.n_items)

    train_s, val_s, test_s = stream.chronological_split()
    cfg = MDGNNConfig(
        variant=args.model, memory_cell=VARIANT_MEMORY_CELLS[args.model],
        n_nodes=stream.num_nodes, d_edge=stream.feat_dim,
        d_mem=args.d_mem, d_msg=args.d_mem, d_embed=args.d_mem,
        n_layers=args.n_layers, n_heads=args.n_heads,
        use_pres=args.pres, beta=args.beta, delta_mode=args.delta_mode,
        pres_scale=args.pres_scale, use_kernels=args.use_kernels,
        kernels_mode=args.kernels_mode,
        dedup_embed=not args.no_dedup_embed,
        pipeline_depth=args.pipeline_depth, scan_chunk=args.scan_chunk,
        event_store=args.event_store, n_shards=args.n_shards,
        shard_budget=args.shard_budget,
        obs_metrics=args.metrics_out is not None)
    key = jax.random.PRNGKey(args.seed)
    params, _ = init_params(key, cfg)
    state = init_state(cfg)
    opt = adamw(args.lr)
    opt_state = opt.init(params)
    if cfg.n_shards > 1:
        # shard-major-permute the node tables onto the mesh and replicate
        # params/opt state; training then runs unchanged — the engines
        # route through repro.train.routing behind cfg.n_shards
        from repro.train import routing
        state = routing.shard_state(cfg, state)
        params, opt_state = routing.replicate((params, opt_state),
                                              cfg.n_shards)
        print(f"[dist] memory-parallel over {cfg.n_shards} shards "
              f"({len(jax.devices())} devices, "
              f"budget={cfg.shard_budget or 'auto'})")
    # cfg.use_kernels routes the full memory-maintenance step and the
    # embedding attention through the kernel registry (docs/KERNELS.md)
    # inside make_train_step / embed_nodes;
    # cfg.pipeline_depth routes through the staleness-aware pipelined
    # schedule (repro.train.pipeline — depth 0 delegates to the sequential
    # loop, bit-exact);
    # cfg.scan_chunk > 1 routes through the scan-compiled macro-batch
    # engine (repro.train.scan — chunk 1 delegates likewise). The two are
    # mutually exclusive (scan.check_schedule raises early).
    # telemetry (docs/OBSERVABILITY.md): --metrics-out opens the JSONL
    # run-log and turns on host-span recording; --trace-dir wraps the step
    # dispatch in a bounded jax.profiler capture. Neither adds per-step
    # host syncs — the obs series ride the step metrics on device.
    runlog = None
    if args.metrics_out:
        from repro.obs import sink, trace as obs_trace
        obs_trace.enable()
        runlog = sink.RunLog(args.metrics_out, role="train", cfg=cfg,
                             argv=argv)
    tracer = None
    if args.trace_dir:
        from repro.obs import trace as obs_trace
        tracer = obs_trace.StepTraceCapture(args.trace_dir,
                                            n_steps=args.trace_steps)
    step_hook = tracer.wrap if tracer else None
    engine = (scan.ScanEngine(cfg, opt, step_hook=step_hook)
              if cfg.scan_chunk > 1 else None)
    train_step = None if engine else pipeline.make_train_step(cfg, opt)
    if tracer is not None and train_step is not None:
        train_step = tracer.wrap(train_step)
    eval_step = loop.make_eval_step(cfg)

    n_batches = train_s.num_batches(args.batch_size)
    depth = cfg.pipeline_depth
    # depth 0 / scan trains from the materialised list (the historical
    # path); depth >= 1 re-carves batches lazily each epoch with host
    # prefetch, overlapping batch prep with device compute. A store-backed
    # stream never materialises: every epoch re-iterates windowed memmap
    # slices (host prefetch overlaps the window mapping), yielding batches
    # bit-identical to the in-RAM carve (docs/DATA.md)
    if streamed or depth:
        make_batches = lambda: train_s.prefetch_batches(
            args.batch_size, depth=max(2, depth))
    else:
        batches = train_s.temporal_batches(args.batch_size)
        make_batches = lambda: batches
    if streamed:
        make_val_batches = lambda: val_s.iter_temporal_batches(
            args.batch_size)
    else:
        val_batches = val_s.temporal_batches(args.batch_size)
        make_val_batches = lambda: val_batches
    history = []
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        pol = kops.execution_policy()
        print(f"[kernels] backend={pol['backend']} mode={cfg.kernels_mode} "
              f"default={pol['default_mode']} "
              f"autotune_entries={pol['autotune_entries']}")
    source = (f"store {args.event_store}" if streamed
              else args.csv or args.dataset)
    print(f"[train] {args.model}{'-PRES' if args.pres else ''} on "
          f"{source}: {len(train_s)} events, K={n_batches} batches "
          f"of b={args.batch_size}"
          + (f", pipeline_depth={depth}" if depth else "")
          + (f", scan_chunk={cfg.scan_chunk}" if cfg.scan_chunk > 1 else ""))
    for epoch in range(args.epochs):
        key, sub = jax.random.split(key)
        if engine is not None:
            params, opt_state, state, res = engine.run_epoch(
                params, opt_state, state, make_batches(), sub, dst_range)
        else:
            params, opt_state, state, res = pipeline.run_epoch(
                params, opt_state, state, make_batches(), cfg, train_step,
                sub, dst_range)
        key, sub = jax.random.split(key)
        vstate, vap, vauc = loop.evaluate(params, state, make_val_batches(),
                                          cfg, eval_step, sub, dst_range)
        history.append({"epoch": epoch, "train_ap": res.ap, "loss": res.loss,
                        "seconds": res.seconds, "val_ap": vap, "val_auc": vauc})
        if runlog is not None:
            from repro.obs import metrics as obs_metrics
            rec = {"epoch": epoch, "loss": res.loss, "train_ap": res.ap,
                   "val_ap": vap, "val_auc": vauc, "seconds": res.seconds,
                   "route_overflow": res.route_overflow}
            if res.obs is not None:
                rec.update(steps=res.obs["steps"], series=res.obs["series"])
                ev = sum(res.obs["series"].get("events", []))
                if res.seconds > 0:
                    rec["events_per_sec"] = ev / res.seconds
                if "route_overflow_shards" in res.obs:
                    rec["route_overflow_shards"] = \
                        res.obs["route_overflow_shards"]
            if cfg.use_pres and cfg.n_shards == 1:
                # per-epoch tracker-health probe (one fetch, between steps)
                rec["gmm_health"] = obs_metrics.gmm_health(state["pres"])
            runlog.write("epoch", **rec)
        print(f"  epoch {epoch}: loss={res.loss:.4f} train_ap={res.ap:.4f} "
              f"val_ap={vap:.4f} val_auc={vauc:.4f} ({res.seconds:.1f}s)")
    if tracer is not None:
        tracer.stop()
    if cfg.n_shards > 1:
        # back to the natural single-device layout so checkpoints are
        # interchangeable with (and restorable by) unsharded runs
        from repro.train import routing
        state = routing.unshard_state(cfg, state)
        params = jax.device_get(params)
    if args.checkpoint:
        save_checkpoint(args.checkpoint, {"params": params, "state": state})
        print(f"[ckpt] saved to {args.checkpoint}")
    if runlog is not None:
        # close() appends the telemetry epilogue: host spans (prefetch
        # waits, store windowing, checkpoint IO), the kernel-dispatch
        # table, and the end marker
        runlog.close()
        print(f"[obs] run-log written to {args.metrics_out}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"config": dataclasses.asdict(cfg), "history": history}, f,
                      indent=2, default=str)
    return history


if __name__ == "__main__":
    main()
