"""Finds a cell's parts by name: `BENCHMARK.json` names the configuration
and the traffic mix of each workload; the configuration lives in
`bench/configs/<config>.json`, the traffic in `bench/workloads/<traffic>.json`,
the limits of the correctness check in `bench/limits/<workload>.json`, and
each per-layer metric's reader in `bench/metrics/<metric>.py`. A new cell
or metric is new files and new entries, never an edit."""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, spec: dict | None = None) -> dict:
    """Everything one cell needs: its entry, configuration, traffic,
    limits, and the metrics it reports."""
    spec = spec or benchmark()
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; known: {sorted(entries)}")
    w = entries[name]

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {
        "entry": w,
        "config": load_json(BENCH / "configs" / f"{w['config']}.json"),
        "traffic": load_json(BENCH / "workloads" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def metric_reader(name: str):
    """The `read(ctx)` function of `bench/metrics/<name>.py`."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
