#!/usr/bin/env python3
"""Finds a serving cell's knee once, on the chip: the highest arrival rate
the engine sustains without a growing backlog. Not part of a run.

    python3 bench/knee.py --workload <name> --rates 40000,60000 --seeds 2

For each seed and rate, the engine serves a window of --seconds at that
rate and the sweep prints the query and ingest p95, how late the loop ran,
and the backlog trend: the median ingest latency of the window's last tenth
over its first tenth. A rate is sustained on a seed where the trend is at
most TREND_MAX and the query p95 at most P95_MAX times the lowest rate's on
that seed: past the knee the queue grows through the window, which shows
in the trend, in the tail, or in both. The knee is the highest rate that
every seed sustains, with every lower rate sustained too.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TREND_MAX = 1.10
P95_MAX = 2.0


def sustained(rows: list) -> dict:
    """{rate: sustained on this seed} for one seed's rows, lowest rate
    first."""
    rows = sorted(rows, key=lambda r: r["rate"])
    base = rows[0]["query_p95_ms"]
    return {r["rate"]: (r["backlog_trend"] <= TREND_MAX
                        and r["query_p95_ms"] <= P95_MAX * base)
            for r in rows}


def knee(by_seed: dict) -> float | None:
    """The highest rate every seed sustains, all lower rates included."""
    verdicts = [sustained(rows) for rows in by_seed.values()]
    best = None
    for rate in sorted(verdicts[0]):
        if not all(v.get(rate, False) for v in verdicts):
            break
        best = rate
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=3_300_000_000)
    args = ap.parse_args(argv)

    import numpy as np
    from bench.lib import cell, device, serve

    spec = cell.workload(args.workload)
    device.enable_compile_cache()
    devs = device.check_devices(spec["entry"]["chips"])
    rates = sorted(float(r) for r in args.rates.split(","))
    by_seed: dict = {}
    for i in range(args.seeds):
        seed = args.first_seed + 104_729 * i
        for rate in rates:
            out = serve.run_cell(spec["config"], spec["traffic"],
                                 spec["limits"], seed, args.seconds, False,
                                 devs, time.perf_counter(), rate=rate,
                                 check=False)
            lat = out["i_lat"]
            tenth = max(1, len(lat) // 10)
            row = {
                "seed": seed, "rate": rate, "events": out["n_due"],
                "query_p95_ms": out["metrics"]["serve_query_p95_ms"]["value"],
                "ingest_p95_ms":
                    out["metrics"]["serve_ingest_p95_ms"]["value"],
                "query_p50_ms": float(np.median(out["q_lat"])),
                "late_ms": out["late_ms"],
                "backlog_trend": float(np.median(lat[-tenth:])
                                       / np.median(lat[:tenth])),
                "compiles_in_window": out["compiles_in_window"]}
            by_seed.setdefault(seed, []).append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"sustained": {s: sustained(r)
                                    for s, r in by_seed.items()},
                      "knee": knee(by_seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
