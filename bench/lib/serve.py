"""Serving cells: the program's `ServeEngine` under an open loop of event
arrivals, driven through `ingest`, `query` and `recommend_topk`.

Set-up makes random weights from the seed, folds a prefix of the stream
into the engine's state in batches of the largest bucket, and warms every
bucket of every kind. The window then offers events at a fixed rate: their
due times are a Poisson clock from the run's start. Each event brings two
link queries, (src, dst) and (src, a random item), answered before the
event is folded (score-then-fold); every `topk_every`-th event also asks
for the top-k items of its source. Each round of the loop hands everything
due to the engine in one call per kind: query, then top-k, then ingest. A
query's latency is the time its answer was back on the host minus its due
time; an event's ingest latency the time its fold had finished on the
device minus its due time.

After the window the reference replays the same rounds from the same
weights and prefix (bench/lib/reference.py, in blocks of the same event
counts), and the answers and the final state are compared.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import cell, compare, device, reference, streams
from bench.lib.train import (extra_tables, program_config, program_state,
                             seed_key)

# the engine's request buckets (the program's MicroBatcher default), which
# the reference pads to as well, so that neither compiles in the window
BUCKETS = (16, 64, 256, 1024)
# a traced run serves a window of at most this many seconds: the profiler
# slows the host, and its trace grows with every round
TRACE_SECONDS = 10.0


def _bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(n)


def _chunks(a: int, b: int):
    for lo in range(a, b, BUCKETS[-1]):
        yield lo, min(lo + BUCKETS[-1], b)


def make_stream(traffic: dict, seed: int, seconds: float, rate: float):
    """Prefix and window events, the negatives' items and the due times."""
    g = traffic["graph"]
    n_window = int(rate * seconds * 1.05) + 64
    n = traffic["prefix_events"] + n_window
    src, dst, t, feat = streams.stream(g["n_users"], g["n_items"], n,
                                       g["feat_dim"], seed,
                                       exponent=g["exponent"],
                                       noise=g["noise"], dt=g["dt"])
    rng = np.random.default_rng([seed, 2])
    neg = (g["n_users"] + rng.integers(0, g["n_items"], n)).astype(np.int32)
    due = streams.poisson_arrival_clock(n_window, rate, seed=seed % 2**32)
    return (src, dst, t, feat), neg, due


def run_cell(config: dict, traffic: dict, limits: dict, seed: int,
             seconds: float, trace: bool, devs, t_start: float,
             trace_dir=None, rate: float | None = None, fault=None,
             check: bool = True) -> dict:
    """One run of a serving cell. `fault(engine)` plants a fault under the
    harness (the benchmark's own tests); `rate` and `check=False` serve the
    knee sweep (bench/knee.py), which reads latencies only."""
    from repro.kernels import ops as kops
    from repro.models import mdgnn
    from repro.serve import MicroBatcher, ServeEngine

    counter = device.compile_counter()
    arch = cell.config_module(config["name"])
    g = traffic["graph"]
    rate = rate or traffic["rate_events_per_s"]
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    k, every = traffic["topk"], traffic["topk_every"]
    items = (g["n_users"], g["n_users"] + g["n_items"])
    cfg = program_config(config, traffic)
    key = seed_key(seed)
    n_pre = traffic["prefix_events"]

    # ---------------------------------------------------------- set-up --
    phases = {"start": time.perf_counter() - t_start}
    with jax.profiler.TraceAnnotation("bench.setup"):
        (src, dst, t, feat), neg, due = make_stream(traffic, seed, seconds,
                                                    rate)
        phases["stream"] = time.perf_counter() - t_start
        params = reference.init_params(arch, jax.random.fold_in(key, 0),
                                       config["model"], g["feat_dim"])
        params0 = jax.device_get(params)
        engine = ServeEngine(cfg, params, mdgnn.init_state(cfg),
                             batcher=MicroBatcher(BUCKETS, g["feat_dim"]),
                             item_range=items)
        if fault is not None:
            fault(engine)
        kops.reset_dispatch_log()
        phases["engine"] = time.perf_counter() - t_start
        engine.warmup(query=True, topk_k=k)
        phases["warmup"] = time.perf_counter() - t_start
        for lo, hi in _chunks(0, n_pre):
            engine.ingest(src[lo:hi], dst[lo:hi], t[lo:hi], feat[lo:hi])
        engine.block_until_ready()
        gc.collect()
    setup_s = time.perf_counter() - t_start
    phases["prefix"] = setup_s
    phases["compiling"] = counter.seconds

    # ---------------------------------------------------------- window --
    if trace:
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=device.profile_options())
    counter.active = True
    n_due = int(np.searchsorted(due, seconds, side="left"))
    q_done = np.full(n_due, np.nan)
    i_done = np.full(n_due, np.nan)
    rounds, topk_out, q_out = [], [], []
    late = []
    handled = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while handled < n_due:
            now = time.perf_counter() - t0
            j = min(int(np.searchsorted(due, now, side="right")), n_due)
            if j <= handled:
                time.sleep(max(0.0, min(due[handled] - now, 0.001)))
                continue
            late.append(now - due[handled])
            a, b = n_pre + handled, n_pre + j
            with jax.profiler.TraceAnnotation("bench.query"):
                scores = engine.query(np.concatenate([src[a:b], src[a:b]]),
                                      np.concatenate([dst[a:b], neg[a:b]]),
                                      np.concatenate([t[a:b], t[a:b]]))
            q_done[handled:j] = time.perf_counter() - t0
            ask = np.arange(a, b)[(np.arange(a, b) - n_pre) % every == 0]
            if len(ask):
                with jax.profiler.TraceAnnotation("bench.topk"):
                    vals, ids = engine.recommend_topk(src[ask], t[ask], k)
                topk_out.append((ask, vals, ids))
            with jax.profiler.TraceAnnotation("bench.ingest"):
                engine.ingest(src[a:b], dst[a:b], t[a:b], feat[a:b])
                engine.block_until_ready()
            i_done[handled:j] = time.perf_counter() - t0
            rounds.append((a, b))
            q_out.append(scores)
            handled = j
    elapsed = time.perf_counter() - t0
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = counter.count
    q_lat = (q_done - due[:n_due]) * 1e3
    i_lat = (i_done - due[:n_due]) * 1e3
    metrics = {
        "serve_query_p95_ms": {"value": float(np.percentile(q_lat, 95)),
                               "unit": "ms"},
        "serve_ingest_p95_ms": {"value": float(np.percentile(i_lat, 95)),
                                "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"}}
    peak = device.memory_peak_bytes(devs)
    dispatch = kops.dispatch_log()
    prog_state = jax.device_get(program_state(
        engine.state, extra_tables(arch, config["model"])))
    calls = {"ingest": len(rounds), "query": len(rounds),
             "topk": len(topk_out)}
    del engine, params
    gc.collect()

    # ----------------------------------------------------------- check --
    if not check:
        return {"metrics": metrics, "late_ms": late_stats(late, rounds),
                "q_lat": q_lat, "i_lat": i_lat, "n_due": n_due,
                "compiles_in_window": compiles_in_window}
    ref = replay(config, traffic, params0, (src, dst, t, feat), neg, n_pre,
                 rounds, [a for a, _, _ in topk_out], k, items)
    numbers = serve_numbers(
        {"scores": np.concatenate(q_out),
         "topk_vals": [v for _, v, _ in topk_out],
         "topk_ids": [i for _, _, i in topk_out], "state": prog_state},
        ref, k, arch.EXACT)
    correct, checks = compare.judge(numbers, limits["limits"])
    not_compiled = {kk: v for kk, v in dispatch.items()
                    if set(v) != {"compiled"}}
    return {
        "numbers": numbers, "ref": ref,
        "correct": bool(correct and not not_compiled
                        and compiles_in_window == 0),
        "attempted": int(n_due * 2 + sum(len(a) for a, _, _ in topk_out)),
        "failed": 0, "metrics": metrics, "peak": peak,
        "traced": ({"dir": trace_dir, "span": "bench.window",
                    "calls": calls}
                   if trace else None),
        "checks": checks, "where": {}, "dispatch": dispatch,
        "not_compiled": not_compiled,
        "compiles_in_window": compiles_in_window,
        "late_ms": late_stats(late, rounds), "setup_phases": phases,
        "rate": rate, "n_due": n_due, "elapsed": elapsed,
        "rounds": rounds, "topk_asks": [a for a, _, _ in topk_out],
        "params0": params0, "stream": (src, dst, t, feat), "neg": neg,
        "q_lat": q_lat, "i_lat": i_lat,
    }


def late_stats(late, rounds) -> dict:
    """How late the loop took up the oldest due event, per round (ms)."""
    return {"mean": float(np.mean(late) * 1e3),
            "max": float(np.max(late) * 1e3), "rounds": len(rounds)}


def replay(config, traffic, params0, stream, neg, n_pre, rounds, topk_asks,
           k, items, dtype=jnp.float32) -> dict:
    """The reference over the same rounds: the prefix folded in blocks of
    the largest bucket, then per round the link scores, the top-k answers
    and the fold, each padded to a bucket with masked rows."""
    arch = cell.config_module(config["name"])
    m = config["model"]
    cfg = {"pres_clip": m["pres_clip"], "beta": m["beta"]}
    src, dst, t, feat = stream
    g = traffic["graph"]
    n_nodes = g["n_users"] + g["n_items"]
    params = jax.tree.map(lambda p: jnp.asarray(p).astype(dtype), params0)
    state = reference.init_state(arch, n_nodes, m, dtype)
    fold = jax.jit(lambda p, s, e: reference.fold(arch, m, cfg, dtype, p, s,
                                                  e))
    score = jax.jit(lambda p, s, a, b_, c: reference.link_scores(
        arch, m, dtype, p, s, a, b_, c))
    item_ids = jnp.arange(items[0], items[1], dtype=jnp.int32)
    every = jax.jit(lambda p, s, a, c: reference.item_scores(
        arch, m, dtype, p, s, a, c, item_ids))

    def pad(a, n):
        return np.concatenate([a, np.zeros((n - len(a),) + a.shape[1:],
                                           a.dtype)])

    def fold_block(state, lo, hi):
        b = _bucket(hi - lo)
        ev = {"src": pad(src[lo:hi], b), "dst": pad(dst[lo:hi], b),
              "t": pad(t[lo:hi], b), "feat": pad(feat[lo:hi], b),
              "mask": np.arange(b) < hi - lo}
        return fold(params, state, ev)

    prec = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    scores, tk_scores = [], []
    with jax.default_matmul_precision(prec):
        for lo, hi in _chunks(0, n_pre):
            state = fold_block(state, lo, hi)
        asks = iter(topk_asks)
        for a, b in rounds:
            qs = np.concatenate([src[a:b], src[a:b]])
            qd = np.concatenate([dst[a:b], neg[a:b]])
            qt = np.concatenate([t[a:b], t[a:b]])
            for lo, hi in _chunks(0, len(qs)):
                n = _bucket(hi - lo)
                out = score(params, state, pad(qs[lo:hi], n),
                            pad(qd[lo:hi], n), pad(qt[lo:hi], n))
                scores.append(np.asarray(out, np.float64)[:hi - lo])
            ask = (np.arange(a, b)[(np.arange(a, b) - n_pre)
                                   % traffic["topk_every"] == 0])
            if len(ask):
                # one top-k request per round, in blocks of the largest
                # bucket, the items embedded at each block's latest time
                assert np.array_equal(next(asks), ask)
                blocks = []
                for lo, hi in _chunks(0, len(ask)):
                    n = _bucket(hi - lo)
                    full = every(params, state, pad(src[ask[lo:hi]], n),
                                 pad(t[ask[lo:hi]], n))
                    blocks.append(np.asarray(full, np.float64)[:hi - lo])
                tk_scores.append(np.concatenate(blocks))
            for lo, hi in _chunks(a, b):
                state = fold_block(state, lo, hi)
    top = [np.argsort(-f, axis=1, kind="stable")[:, :k] for f in tk_scores]
    return {"scores": np.concatenate(scores) if scores else np.zeros(0),
            "topk_all": tk_scores, "item_lo": items[0],
            "topk_ids": [i + items[0] for i in top],
            "topk_vals": [np.take_along_axis(f, i, axis=1)
                          for f, i in zip(tk_scores, top)],
            "state": jax.device_get(state)}


def serve_numbers(prog: dict, ref: dict, k: int, exact=()) -> dict:
    """The numbers compared for a serving cell:

    * `score_gap`: the widest |link score - reference score|;
    * `topk_rank_gap`: the widest gap by which the reference score of an
      item the program put in a top-k lies below the reference's k-th
      best score (0 where the reference agrees it belongs there, so ties
      broken the other way cost nothing);
    * `topk_score_gap`: the widest |top-k score - reference score of the
      same item|;
    * `state_gap` and `state_mismatch`: the state after the window, as
      for training (`compare.state_numbers`, with the module's `exact`
      tables).
    """
    scores = np.asarray(prog["scores"], np.float64)
    sg = float(np.max(np.abs(scores - ref["scores"]))) if len(scores) else 0.0
    rank_gap, score_gap = 0.0, 0.0
    for ids, vals, full in zip(prog["topk_ids"], prog["topk_vals"],
                               ref["topk_all"]):
        got = np.take_along_axis(full, np.asarray(ids) - ref["item_lo"],
                                 axis=1)
        kth = np.sort(full, axis=1)[:, -k][:, None]
        rank_gap = max(rank_gap, float(np.max(kth - got)))
        score_gap = max(score_gap, float(np.max(np.abs(
            np.asarray(vals, np.float64) - got))))
    state, _ = compare.state_numbers(prog["state"], ref["state"], exact)
    return {"score_gap": sg, "topk_rank_gap": rank_gap,
            "topk_score_gap": score_gap, **state}
