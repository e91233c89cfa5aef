#!/usr/bin/env python3
"""Runs one cell of the chip benchmark with the program's host spans on,
and reports what they saw.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> \
        [--trace 0|1] [--check 0|1]

The cell runs as `bench/run.py` runs it (the same `run_cell`, program path
and check), with `repro.obs.trace` enabled from the start of the run to
its end; `bench/run.py` is the same cell with the spans off, so the two
side by side price them. The last line of stdout is one JSON object:

* the cell's end-to-end metrics and `correct` (an untraced serving run
  with `--check 0` skips the reference and reports no `correct`);
* the in-memory spans summed per name (count, total, longest), the
  longest spans with their arguments (the stall finder), and the
  counters; serving gives the share of the rows sent that were padding,
  from the counters, over the whole run;
* with `--trace 1`: the traced window's device idle, the share of it that
  falls inside the program's spans, and the idle inside each span name
  (and, serving, each request kind) per training step or serving round.
  Training reads the whole traced epoch; serving reads the idle inside
  the benchmark's `bench.query`, `bench.topk` and `bench.ingest` calls.

Without a TPU the run exits 3 and prints no result, as `bench/run.py`
does. This tool stands in until the benchmark's traced runs enable the
spans and its per-layer metrics read them (PERF.md, Open questions);
then it and `bench/lib/programtrace.py` go.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SERVE_CALLS = ("bench.query", "bench.topk", "bench.ingest")


def pad_share(counts: dict):
    """Percent of the rows sent that were bucket padding, from the serve
    engine's counters; None where nothing was sent."""
    sent = counts.get("serve.rows_sent", 0)
    return (100.0 * (1.0 - counts.get("serve.rows_valid", 0) / sent)
            if sent else None)


def idle_report(kind: str, trace: dict, ops: list, win: tuple, prog: list,
                per: int) -> dict:
    """Idle of the traced window and its attribution to program spans,
    in ms per step (training) or per round (serving)."""
    from bench.lib import programtrace as pt
    gaps = pt.gaps(ops, win)
    if kind == "serve":
        calls = [h for h in trace["host"] if h["name"] in SERVE_CALLS]
        gaps = pt.inside(gaps, calls)
    idle = pt.total(gaps)
    by_name: dict = {}
    for s in prog:
        by_name.setdefault(s["name"], []).append(s)
        if "kind" in s["args"]:
            by_name.setdefault(f"{s['name']}[{s['args']['kind']}]",
                               []).append(s)
    out = {"window_idle_ms": pt.total(pt.gaps(ops, win)) * 1e-6,
           "idle_ms": idle * 1e-6, "per": per,
           "covered_share": (pt.total(pt.inside(gaps, prog)) / idle
                             if idle else None),
           "idle_ms_per": {k: pt.total(pt.inside(gaps, v)) * 1e-6 / per
                           for k, v in sorted(by_name.items())},
           "spans_in_window": {k: sum(win[0] <= x["start_ns"] < win[1]
                                      for x in v)
                               for k, v in sorted(by_name.items())}}
    return out


def span_report(spans: list, top: int = 5) -> dict:
    from repro.obs import trace as obs_trace
    longest = sorted(spans, key=lambda s: -s["dur_s"])[:top]
    return {"summary": obs_trace.span_summary(spans),
            "longest": longest}


def run(spec: dict, devs, seed: int, seconds: float, trace: bool,
        check: bool, trace_dir, t_start: float, chip=None) -> dict:
    """One run of the cell `spec` (`bench.lib.cell.workload`), its spans
    on from start to end; the result line as a dict. `chip` stands in for
    the peaks of a device the table does not know (tests)."""
    from bench.lib import layers, programtrace, serve, train
    from repro.obs import trace as obs_trace

    kind = spec["traffic"]["kind"]
    run_cell = {"train": train.run_cell, "serve": serve.run_cell}[kind]
    kw = {"check": check} if kind == "serve" else {}
    obs_trace.enable()
    try:
        out = run_cell(spec["config"], spec["traffic"], spec["limits"],
                       seed, seconds, trace, devs, t_start,
                       trace_dir=trace_dir, **kw)
    finally:
        obs_trace.disable()
    result = {"trace": int(trace),
              "metrics": {k: v["value"] for k, v in out["metrics"].items()},
              "compiles_in_window": out["compiles_in_window"]}
    for key in ("correct", "late_ms"):
        if key in out:
            result[key] = out[key]
    result["host"] = span_report(obs_trace.drain())
    result["counts"] = obs_trace.drain_counts()
    if kind == "serve":
        result["pad_share"] = pad_share(result["counts"])
    if trace:
        ctx = layers.context(spec, out, devs, chip)
        prog = programtrace.extract(out["traced"]["dir"])
        per = ctx.steps if kind == "train" else ctx.calls["query"]
        result["idle"] = idle_report(kind, ctx.trace, ctx.ops, ctx.win,
                                     prog, per)
        result["window_s"] = ctx.window_ns * 1e-9
        if kind == "train":
            result["stage_ms"] = {
                s: ctx.tr.scope_ns(ctx.ops, ctx.scopes, s) * 1e-6 / ctx.steps
                for s in ("memory_update", "embed", "maintain_state")}
        shutil.rmtree(out["traced"]["dir"], ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--check", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    if args.trace and not args.check:
        ap.error("a traced run keeps its check (--check 1)")

    from bench.lib import cell, device

    spec = cell.workload(args.workload)
    device.enable_compile_cache()
    try:
        devs = device.check_devices(spec["entry"]["chips"])
    except device.NoChip as e:
        print(f"[spans] {e}", file=sys.stderr, flush=True)
        return 3
    result = run(spec, devs, args.seed, args.seconds, bool(args.trace),
                 bool(args.check),
                 ROOT / ".bench_trace" / f"{args.workload}.spans", T_START)
    result.update(workload=args.workload, seed=args.seed)
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
