"""Per-layer metrics of a traced run: the trace is reduced once
(`tracereduce`) and each of the cell's per-layer metrics is read by its own
reader, `bench/metrics/<name>.py`, from the context built here. A reader
that finds nothing to read returns None, and the metric is left out."""
from __future__ import annotations

import types

from bench.lib import cell, flops, peaks, tracereduce


def context(spec: dict, out: dict, devs, chip=None) -> types.SimpleNamespace:
    traced = out["traced"]
    trace = tracereduce.extract(traced["dir"])
    trace["scopes"] = traced.get("scopes", {})
    win = tracereduce.window(trace, traced["span"])
    ops = tracereduce.in_window(trace["device"], win)
    return types.SimpleNamespace(
        trace=trace, ops=ops, win=win, window_ns=win[1] - win[0],
        scopes=trace["scopes"],
        steps=traced.get("steps"), steps_per_s=traced.get("steps_per_s"),
        written_per_step=traced.get("written_per_step"),
        calls=traced.get("calls"),
        model=spec["config"]["model"], traffic=spec["traffic"],
        arch=cell.config_module(spec["config"]["name"]),
        chips=len(devs),
        peaks=chip or peaks.chip_peaks(devs[0].device_kind),
        tr=tracereduce, flops=flops)


def read_all(spec: dict, out: dict, devs, chip=None) -> tuple:
    """({metric: {"value", "unit"}}, busy_s, window_s, breakdown). `chip`
    stands in for the peaks of a device the table does not know (tests)."""
    ctx = context(spec, out, devs, chip)
    metrics = {}
    for m in spec["per_layer"]:
        value = cell.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = tracereduce.busy_ns(ctx.ops, ctx.win)
    return (metrics, busy * 1e-9, ctx.window_ns * 1e-9,
            tracereduce.breakdown(ctx.trace, ctx.ops, ctx.win))
