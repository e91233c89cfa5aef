#!/usr/bin/env python3
"""Chip smoke test: the paper's model trains and serves on a TPU through
the normal entry points, with the Pallas kernels compiled by Mosaic.

    python3 chip_smoke.py               # one chip: phases (a)-(c)
    python3 chip_smoke.py --four-chips  # 4 chips: memory-parallel parity

Model: TGN with PRES at `configs/tgn_pres.py::CONFIG`'s widths (d_mem =
d_msg = d_embed = 100, d_time = 32, 10 neighbours, 1 layer, 2 heads,
beta = 0.1) on a seeded JODIE-Wikipedia-shaped stream (8,227 users, 1,000
items, 172-d edge features; `graph/datasets.py::generate`), temporal
batches of 1,000 events (serving: micro-batches of 200). Weights are
random from --seed; nothing is downloaded. Only the number of events is
cut (to what the steps below use), never a width or the node table.

Phases, all in this one process (no subprocesses, no caught failures):

(a) training: STEPS sequential-engine steps through
    `pipeline.make_train_step` / `pipeline.run_epoch` with the kernels on
    (`use_kernels=True`, `kernels_mode="auto"`, which resolves to compiled
    Pallas on a TPU), each checked against the same step with the kernels
    off under `jax.default_matmul_precision("highest")` — the float32
    reference — from the same inputs (`lockstep`). Per-step losses and
    memory tables, the final ones included, are held to TOL_LOSS /
    TOL_MEMORY (see there).
(b) serving: a `ServeEngine` on the trained state ingests the next
    micro-batches and answers link queries, checked against the offline
    evaluator with `serve/parity.py::check_offline_parity`, then answers
    top-k queries checked against its own link scores.
(c) dispatch: every kernel (a) and (b) dispatched resolved to `compiled`
    (`kernels/ops.py::dispatch_log`) — a stray REPRO_KERNELS_MODE or
    autotune file that routed the path elsewhere fails here.

--four-chips runs only memory-parallel training (`n_shards=4`, the
routing protocol of train/routing.py over a 4-device mesh) and, as its
comparison, the same steps with `n_shards=1` on one device, and compares
every step's loss and state in natural layout, the final state included
(as train/mesh_check.py does).

The last line of stdout is one JSON object, printed only when every phase
passed on a TPU: {"ok": true, "device": {"platform", "kind", "count"}}.
On another backend the script exits non-zero without it; `--rehearse`
there runs the phases at a reduced size with the kernels in interpret
mode (CPU rehearsal, e.g. with XLA_FLAGS=
--xla_force_host_platform_device_count=4 for --four-chips) and still
exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# the smoke judges the path users get: an environment override of the
# kernel execution mode would change which path runs
_STRAY_MODE = os.environ.pop("REPRO_KERNELS_MODE", None)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.tgn_pres import CONFIG  # noqa: E402
from repro.graph import datasets  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import mdgnn  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serve import parity  # noqa: E402
from repro.train import pipeline, routing  # noqa: E402

STEPS = 10
# JODIE-Wikipedia node and edge-feature counts; events cut to what the
# phases use (Wikipedia has 157,474)
# Serving ingests micro-batches of 200 (launch/serve.py's default).
FULL = dict(users=8227, items=1000, feat=172, batch=1000, serve_batch=200,
            serve_batches=5)
REHEARSAL = dict(users=200, items=50, feat=172, batch=100, serve_batch=50,
                 serve_batches=4)

# Tolerances (max |difference|) of phase (a): kernels-on at the default
# matmul precision against the float32 reference at "highest". At the
# default precision the TPU rounds float32 matmul inputs to bfloat16
# (relative error 2^-8 ~ 4e-3 per product). Each step starts from the
# same inputs on both sides, so a gap is one step's rounding through a
# chain of such matmuls (message MLP, GRU, attention, decoder). Memory
# rows are GRU/PRES states of magnitude ~1 (the trackers are compared
# relative to their scale), so 5e-2 is ~12 bf16 ulps; the loss (~0.7
# BCE) averages 3,000 scores, so 1e-2 leaves the same margin. A kernel
# bug (a wrong row, a dropped write, a mis-masked slot) moves a memory
# row by O(1).
TOL_LOSS = 1e-2
TOL_MEMORY = 5e-2
# phase (b): serve engine vs offline evaluator — the same kernels and
# precision on both sides
TOL_SERVE = 1e-4
# phase (b): top-k scores (the link_score kernel) vs the engine's link
# scores (XLA's decoder at the default precision, bf16 products): two
# roundings of a 2 x 100-wide MLP on scores of magnitude ~1, each off by
# up to a few bf16 half-ulps (2^-9 ~ 2e-3)
TOL_TOPK = 1e-2
# --four-chips: 4 shards vs 1 device per step, the same kernels and
# precision on both sides, so only float32 summation order differs (the
# routed exchange moves rows exactly)
TOL_SHARDS = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def make_stream(size: dict, seed: int):
    cut = size["batch"] * (STEPS + 1)
    n_events = cut + size["serve_batch"] * size["serve_batches"]
    spec = datasets.SyntheticSpec("jodie-wikipedia", size["users"],
                                  size["items"], n_events, size["feat"])
    stream = datasets.generate(spec, seed=seed)
    dst_range = (spec.n_users, spec.n_users + spec.n_items)
    return stream.slice(0, cut), stream.slice(cut, len(stream)), dst_range


def _place(cfg, params, opt_state, state):
    """Host-side (params, optimizer state, natural-layout state) -> the
    device layout cfg trains in (fresh buffers: the step donates them)."""
    if cfg.n_shards > 1:
        params, opt_state = routing.replicate((params, opt_state),
                                              cfg.n_shards)
        return params, opt_state, routing.shard_state(cfg, state)
    return jax.device_put((params, opt_state, state))


def _natural(cfg, state):
    if cfg.n_shards > 1:
        state = routing.unshard_state(cfg, state)
    return jax.device_get(state)


def lockstep(cfg, ref_cfg, batches, dst_range, seed: int, ref_ctx):
    """STEPS lag-one training steps from a fresh seeded init, each through
    `pipeline.run_epoch` as launch/train.py drives an epoch: one two-batch
    window per call, with the epoch's per-step PRNG key, so the chain of
    calls is exactly one epoch over `batches`. Before each step the
    reference (`ref_cfg`, run under `ref_ctx`) takes the same step from
    the same params, optimizer state and memory, and the run goes on from
    cfg's result. Comparing step by step keeps the check on the kernels:
    ten free-running Adam steps amplify any float32 rounding difference
    (a 1e-7 relative nudge of the initial weights moves the final memory
    by ~0.5 in float32 on the CPU), so their end states differ whatever
    the kernels do.

    Returns (params, natural-layout state, rows) with one row per step:
    (loss, reference loss, {state leaf: max |difference| relative to
    max(1, max |reference leaf|)})."""
    params, _ = mdgnn.init_params(jax.random.PRNGKey(seed), cfg)
    opt = adamw(1e-3)
    # host copies: each step places its own device buffers
    params, opt_state, state = jax.device_get(
        (params, opt.init(params), mdgnn.init_state(cfg)))
    step = pipeline.make_train_step(cfg, opt)
    ref_step = pipeline.make_train_step(ref_cfg, opt)
    key = jax.random.PRNGKey(seed + 1)
    rows = []
    t0 = time.perf_counter()
    for i in range(STEPS):
        window = batches[i:i + 2]
        with ref_ctx:
            _, _, ref_state, ref_res = pipeline.run_epoch(
                *_place(ref_cfg, params, opt_state, state), window, ref_cfg,
                ref_step, key, dst_range)
        params, opt_state, state, res = pipeline.run_epoch(
            *_place(cfg, params, opt_state, state), window, cfg, step, key,
            dst_range)
        params, opt_state = jax.device_get((params, opt_state))
        state = _natural(cfg, state)
        rows.append((res.loss, ref_res.loss,
                     max_state_diff(state, _natural(ref_cfg, ref_state))))
        key = jax.random.split(key)[0]
    log(f"    {STEPS} steps (and their references) in "
        f"{time.perf_counter() - t0:.1f}s wall (compile included; not a "
        f"speed measurement)")
    losses = np.array([r[0] for r in rows])
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite training loss: {losses}")
    return params, state, rows


def max_abs(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def max_state_diff(a, ref) -> dict:
    """Per leaf of two natural-layout states: max |a - ref| relative to
    max(1, max |ref|) (the PRES trackers hold sums that grow past 1)."""
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    return {jax.tree_util.keystr(p): max_abs(x, y) / max(
                1.0, float(np.max(np.abs(np.asarray(y, np.float64)))))
            for (p, x), y in zip(la, jax.tree.leaves(ref)) if np.size(x)}


def check(name: str, value: float, tol: float) -> None:
    ok = value <= tol
    log(f"    {name}: {value:.3e} (tolerance {tol:.0e}) "
        f"{'ok' if ok else 'FAILED'}")
    if not ok:
        raise AssertionError(f"{name} {value:.3e} exceeds {tol:.0e}")


def report(rows, names) -> None:
    log(f"    step  loss({names[0]})  loss({names[1]})  "
        f"max state difference")
    for i, (a, b, diffs) in enumerate(rows):
        leaf, worst = max(diffs.items(), key=lambda kv: kv[1])
        log(f"    {i:4d}  {a:.8f}  {b:.8f}  {worst:.3e} {leaf}")


def phase_train(cfg, train_s, dst_range, seed: int):
    batch = len(train_s) // (STEPS + 1)
    log(f"(a) training: {STEPS} steps over {len(train_s)} events in "
        f"batches of {batch}; kernels on (use_kernels=True, "
        f"kernels_mode={cfg.kernels_mode}) against the reference: kernels "
        f"off, matmul precision 'highest', from the same inputs each step")
    ref_cfg = dataclasses.replace(cfg, use_kernels=False)
    params, state, rows = lockstep(
        cfg, ref_cfg, train_s.temporal_batches(batch), dst_range, seed,
        jax.default_matmul_precision("highest"))
    report(rows, ("kernels", "reference"))
    check("max |loss - reference loss|",
          max(abs(a - b) for a, b, _ in rows), TOL_LOSS)
    check("max |memory - reference memory| (every step, final included)",
          max(d["['memory'].mem"] for _, _, d in rows), TOL_MEMORY)
    return params, state


def phase_serve(cfg, params, state, serve_s, dst_range, batch: int):
    log(f"(b) serving: ServeEngine over {len(serve_s)} further events in "
        f"micro-batches of {batch}")
    t0 = time.perf_counter()
    state = jax.device_put(state)
    diff, n_scored, eng = parity.check_offline_parity(
        cfg, params, state, serve_s, dst_range, batch_size=batch)
    log(f"    {n_scored} link queries scored against the offline evaluator")
    check("max |engine score - evaluator score|", diff, TOL_SERVE)
    srcs = np.asarray(serve_s.src[:16])
    t = np.full(len(srcs), float(serve_s.t[-1]), np.float32)
    k = 10
    vals, ids = eng.recommend_topk(srcs, t, k)
    items = np.arange(*dst_range, dtype=np.int32)
    worst = 0.0
    for i, s in enumerate(srcs):
        # link scores over all items, in chunks of the micro-batch size so
        # they reuse the query program the parity check compiled
        full = np.concatenate([
            eng.query(np.full(len(c), s, np.int32), c,
                      np.full(len(c), t[i], np.float32))
            for c in np.array_split(items, -(-len(items) // batch))])
        at_ids = full[ids[i] - dst_range[0]]
        worst = max(worst, float(np.max(np.abs(vals[i] - at_ids))))
        # the returned items are a top-k of the full link scores
        kth = np.sort(full)[-k]
        if np.min(at_ids) < kth - TOL_TOPK:
            raise AssertionError(f"top-k for source {s} misses a better "
                                 f"item: {np.min(at_ids)} < {kth}")
    log(f"    top-{k} over {len(items)} items for {len(srcs)} sources")
    check("max |top-k score - link score|", worst, TOL_TOPK)
    log(f"    serving took {time.perf_counter() - t0:.1f}s wall (compile "
        f"included; not a speed measurement)")


def phase_dispatch(want_mode: str, expected: set) -> None:
    table = kops.dispatch_log()
    log(f"(c) kernel dispatch: {json.dumps(table, sort_keys=True)}")
    stray = {k: v for k, v in table.items() if set(v) != {want_mode}}
    missing = expected - set(table)
    if stray or missing:
        raise AssertionError(f"kernels not all {want_mode}: {stray}; "
                             f"never dispatched: {sorted(missing)}")
    log(f"    every dispatched kernel resolved to {want_mode}")


def phase_four_chips(cfg, train_s, dst_range, seed: int) -> None:
    n = 4
    mesh = routing.get_mesh(n)
    ids = sorted({d.id for d in mesh.devices.flat})
    log(f"(4) memory-parallel training over a {mesh.devices.size}-device "
        f"mesh, devices {ids}")
    if len(ids) != n:
        raise AssertionError(f"mesh spans {len(ids)} devices, not {n}")
    batch = len(train_s) // (STEPS + 1)
    log(f"  n_shards={n} against n_shards=1 on one device, from the same "
        f"inputs each step, {STEPS} steps in batches of {batch}")
    _, _, rows = lockstep(dataclasses.replace(cfg, n_shards=n), cfg,
                          train_s.temporal_batches(batch), dst_range, seed,
                          contextlib.nullcontext())
    report(rows, ("4 shards", "1 device"))
    log(f"    final per-table difference: {json.dumps(rows[-1][2])}")
    check("max |loss(4 shards) - loss(1 device)|",
          max(abs(a - b) for a, b, _ in rows), TOL_SHARDS)
    check("max |state(4 shards) - state(1 device)| (natural layout)",
          max(max(d.values()) for _, _, d in rows), TOL_SHARDS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard memory-parallel path and "
                         "its 1-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="on a non-TPU backend, run the phases at a "
                         "reduced size in interpret mode (never ok)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if not on_tpu and not args.rehearse:
        log("no TPU found: nothing measured (--rehearse runs the phases "
            "here at a reduced size)")
        return 2
    if _STRAY_MODE is not None:
        log(f"[kernels] ignored REPRO_KERNELS_MODE={_STRAY_MODE!r}")
    log(f"[cache] compilation cache: {compile_cache.enable() or 'off'}")
    size = FULL if on_tpu else REHEARSAL
    # on a TPU "auto" resolves to compiled Pallas. The CPU rehearsal pins
    # interpret mode, except on the 4-device path: JAX's Pallas
    # interpreter cannot run inside a shard_map that checks replication
    # (check_vma), so there the rehearsal covers the mesh and the routing
    # with the jnp oracle, and the kernels are covered on the chip
    if on_tpu:
        mode, want_mode = "auto", "compiled"
    else:
        mode = want_mode = "oracle" if args.four_chips else "interpret"
    train_s, serve_s, dst_range = make_stream(size, args.seed)
    cfg = dataclasses.replace(
        CONFIG, n_nodes=size["users"] + size["items"], d_edge=size["feat"],
        use_kernels=True, kernels_mode=mode, obs_metrics=True)
    log(f"[config] tgn-pres n_nodes={cfg.n_nodes} d_mem={cfg.d_mem} "
        f"d_msg={cfg.d_msg} d_embed={cfg.d_embed} d_time={cfg.d_time} "
        f"d_edge={cfg.d_edge} K={cfg.n_neighbors} layers={cfg.n_layers} "
        f"heads={cfg.n_heads} beta={cfg.beta} batch={size['batch']}")
    kops.reset_dispatch_log()
    t0 = time.perf_counter()
    if args.four_chips:
        if len(devices) < 4:
            raise SystemExit(f"--four-chips needs 4 devices, found "
                             f"{len(devices)}")
        phase_four_chips(cfg, train_s, dst_range, args.seed)
        phase_dispatch(want_mode, {"memory_update_table", "embed_attn"})
    else:
        params, state = phase_train(cfg, train_s, dst_range, args.seed)
        phase_serve(cfg, params, state, serve_s, dst_range,
                    size["serve_batch"])
        phase_dispatch(want_mode, {"memory_update_table", "embed_attn",
                                   "link_score"})
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s wall")
    if not on_tpu:
        log("rehearsal only: no TPU, so no result")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
