"""Finds a cell's parts by name: `BENCHMARK.json` names the configuration
and the traffic mix of each workload; the configuration lives in
`bench/configs/<config>.json` and its reference module in
`bench/configs/<config>.py`, the traffic in `bench/workloads/<traffic>.json`,
the limits of the correctness check in `bench/limits/<workload>.json`, and
each per-layer metric's reader in `bench/metrics/<metric>.py`. A new cell,
configuration or metric is new files and new entries, never an edit."""
from __future__ import annotations

import functools
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
        "module": config_module(w["config"]),
        "traffic": load_json(BENCH / "workloads" / f"{w['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def _load(path: pathlib.Path, prefix: str):
    mod_spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The `read(ctx)` function of `bench/metrics/<name>.py`."""
    return _load(BENCH / "metrics" / f"{name}.py", "bench_metric_").read


# what a configuration's module gives the shared reference and harness
MODULE_NAMES = ("emb_shapes", "embed", "extra_state", "maintain_extra",
                "EXACT", "embed_flops", "NOT_TAKEN")


def config_module(name: str):
    """The reference module of configuration `name`,
    `bench/configs/<name>.py`: what differs from one MDGNN to another
    (`MODULE_NAMES`), in plain `jax.numpy` and importing nothing of the
    program. Loaded once per file, so the reference's compiled steps,
    cached by module, are reused."""
    return _config_module(BENCH / "configs" / f"{name}.py")


@functools.lru_cache(maxsize=None)
def _config_module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {path.stem!r} has no reference module: "
            f"no file {path}")
    mod = _load(path, "bench_config_")
    missing = [n for n in MODULE_NAMES if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"{path} does not define {', '.join(missing)}")
    return mod
