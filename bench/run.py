#!/usr/bin/env python3
"""Runs one cell of the chip benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

`BENCHMARK.json` names the cell's configuration and traffic mix; their files,
the cell's limits and each per-layer metric's reader are found by name under
`bench/` (see bench/lib/cell.py). With `--trace 0` the last line of stdout
carries the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics, read from a profiler trace of a further stretch. Without a TPU, or
with fewer chips than the cell asks for, the run exits 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the benchmark measures the path users get: an environment override of the
# kernels' execution mode would measure another one
os.environ.pop("REPRO_KERNELS_MODE", None)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import cell, device, layers, serve, train

    spec = cell.workload(args.workload)
    device.enable_compile_cache()
    try:
        devs = device.check_devices(spec["entry"]["chips"])
    except device.NoChip as e:
        log(f"[bench] {e}")
        return 3
    log(f"[bench] device {device.describe(devs)}")
    drivers = {"train": train.run_cell, "serve": serve.run_cell}
    kind = spec["traffic"]["kind"]
    if kind not in drivers:
        raise ValueError(f"no driver for traffic kind {kind!r}")
    trace_dir = ROOT / ".bench_trace" / args.workload
    out = drivers[kind](spec["config"], spec["traffic"], spec["limits"],
                        args.seed, args.seconds, bool(args.trace), devs,
                        T_START, trace_dir=trace_dir)
    log(f"[bench] dispatch {out['dispatch']}")
    if out["not_compiled"]:
        log(f"[bench] NOT COMPILED: {out['not_compiled']}")
    log(f"[bench] compiles in window {out['compiles_in_window']}")
    for key in ("setup_phases", "losses", "ref_losses", "where", "late_ms",
                "rate"):
        if key in out:
            log(f"[bench] {key} {out[key]}")
    dev = device.describe(devs, out["peak"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        per_layer, busy_s, window_s, breakdown = layers.read_all(
            spec, out, devs)
        shutil.rmtree(out["traced"]["dir"], ignore_errors=True)
        result["metrics"] = per_layer
        dev.update(busy_s=busy_s, window_s=window_s)
        result["device"] = dev
        result["breakdown"] = breakdown
    else:
        names = {m["name"] for m in spec["end_to_end"]}
        result["metrics"] = {k: v for k, v in out["metrics"].items()
                             if k in names}
        result["device"] = dev
    result["checks"] = out["checks"]
    for name, c in out["checks"].items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
