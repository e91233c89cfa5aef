#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process on the
chip (the benchmark's own runs never run this).

    python3 bench/calibrate.py --workload <name> --seeds 12 --control-seeds 3

For each seed the program runs the cell as a run does, at the cell's own
size, and is compared with the reference: those are the lower readings
(training: the first steps only, bench/lib/compare.py; serving: a whole
window of --seconds at the cell's rate, bench/lib/serve.py). On the
control seeds stand-ins take the program's place and are compared the
same way:

* the control: the reference computed in bfloat16, the precision below
  the configuration's float32;
* training, the fault `half_batch`: the reference with the loss's mean
  taken over half of each batch;
* training, a diagnosis, `ref_default_precision`: the float32 reference
  at the TPU's default matmul precision (bf16 passes), as the program's
  XLA code runs, which shows how much of the program's gap precision
  alone makes.

With --witness-seeds the training program itself also runs under
`highest` matmul precision: a second witness of where a gap comes from.
A step that returns its state unchanged reads exactly 1 on
`update_norm_gap` and needs no run. Prints one JSON line per reading and
a summary per kind and number, and writes the readings to --out.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.lib import cell, compare, device, reference, serve, train  # noqa: E402

FIRST_SEED = 3_000_000_000    # the readings use seeds FIRST_SEED + 7919 i


def read_train(spec, seed, devs, controls, emit):
    config, traffic = spec["config"], spec["traffic"]
    t0 = time.perf_counter()
    out = train.run_cell(config, traffic, spec["limits"], seed, 0.0, False,
                         devs, time.perf_counter())
    emit({"kind": "program", "seed": seed, "numbers": out["numbers"],
          "where": out["where"], "dispatch": out["dispatch"],
          "losses": out["losses"], "ref_losses": out["ref_losses"],
          "seconds": time.perf_counter() - t0})
    if not controls:
        return
    g = traffic["graph"]
    stream = train.make_stream(traffic, seed)
    key = jax.random.fold_in(train.seed_key(seed), 1)
    dst = (g["n_users"], g["n_users"] + g["n_items"])
    ref = out["ref"]
    arch = spec["module"]
    for kind, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                     ("half_batch", {"half_batch": True}),
                     ("ref_default_precision", {"precision": "default"})):
        steps, p_end, s_end = reference.run(
            arch, train.model_spec(config, traffic), ref["params0"], stream,
            traffic["batch_size"], dst, key, traffic["check_steps"], **kw)
        stand_in = {"losses": [r["loss"] for r in steps],
                    "grads": steps[0]["grads"], "params0": ref["params0"],
                    "params_end": p_end, "state_end": s_end}
        numbers, where = compare.training_numbers(stand_in, ref, arch.EXACT)
        emit({"kind": kind, "seed": seed, "numbers": numbers,
              "where": where})


def read_serve(spec, seed, devs, seconds, controls, emit):
    config, traffic = spec["config"], spec["traffic"]
    t0 = time.perf_counter()
    out = serve.run_cell(config, traffic, spec["limits"], seed, seconds,
                         False, devs, time.perf_counter())
    emit({"kind": "program", "seed": seed, "numbers": out["numbers"],
          "dispatch": out["dispatch"], "late_ms": out["late_ms"],
          "metrics": out["metrics"], "seconds": time.perf_counter() - t0})
    if not controls:
        return
    g = traffic["graph"]
    items = (g["n_users"], g["n_users"] + g["n_items"])
    ctrl = serve.replay(config, traffic, out["params0"], out["stream"],
                        out["neg"], traffic["prefix_events"], out["rounds"],
                        out["topk_asks"], traffic["topk"], items,
                        dtype=jnp.bfloat16)
    emit({"kind": "control_bf16", "seed": seed,
          "numbers": serve.serve_numbers(ctrl, out["ref"], traffic["topk"],
                                         spec["module"].EXACT)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--witness-seeds", type=int, default=0,
                    help="training: on this many seeds also run the program "
                         "under `highest` matmul precision (a diagnosis)")
    ap.add_argument("--seconds", type=float, default=None,
                    help="serving: the window of each reading (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    spec = cell.workload(args.workload)
    seconds = args.seconds or cell.benchmark()["run_seconds"]
    device.enable_compile_cache()
    devs = device.check_devices(spec["entry"]["chips"])
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    for i in range(args.seeds):
        seed = FIRST_SEED + 7919 * i
        controls = i < args.control_seeds
        if spec["traffic"]["kind"] == "serve":
            read_serve(spec, seed, devs, seconds, controls, emit)
            continue
        read_train(spec, seed, devs, controls, emit)
        if i < args.witness_seeds:
            with jax.default_matmul_precision("highest"):
                out = train.run_cell(spec["config"], spec["traffic"],
                                     spec["limits"], seed, 0.0, False, devs,
                                     time.perf_counter())
            emit({"kind": "program_highest", "seed": seed,
                  "numbers": out["numbers"], "where": out["where"]})
    summary: dict = {}
    for r in rows:
        for name, v in r["numbers"].items():
            summary.setdefault(r["kind"], {}).setdefault(name, []).append(v)
    for kind, nums in summary.items():
        for name, vals in nums.items():
            print(f"SUMMARY {kind:22s} {name:24s} n={len(vals):2d} "
                  f"min={min(vals):.4g} median={sorted(vals)[len(vals) // 2]:.4g}"
                  f" max={max(vals):.4g}", flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
