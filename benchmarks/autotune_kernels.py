"""Autotune the kernel registry at the shapes the model actually emits.

For each registered kernel x representative shape this sweeps the execution
modes the backend supports (oracle always; interpret Pallas on CPU; compiled
Pallas + block grid on TPU — repro.kernels.autotune.candidates) and persists
the measured-fastest candidate to results/autotune/<backend>.json, which
`ops.dispatch` consults whenever neither the caller nor REPRO_KERNELS_MODE
pins a mode (docs/KERNELS.md §Execution policy).

The representative shapes mirror the two call-site families the committed
figs exercise: the bench stream (wiki-bench: 520 nodes, batch 100 -> 200
touched occurrences, d_mem 32 — benchmarks/common.bench_stream) and the
launch defaults (d_mem 100, batch 500 -> 1000 occurrences). On a TPU each
winner is stamped with the memory-roofline floor (roofline.kernel_ceiling_ms,
from the chip's published peaks) so an entry sitting orders of magnitude
above bandwidth reads as dispatch overhead at a glance.

    PYTHONPATH=src python -m benchmarks.autotune_kernels [--force] [--fast]
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import common, roofline
from repro.kernels import autotune


def _rng(seed=0):
    return np.random.default_rng(seed)


def _f32(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _memory_update_args(rng, m, d, din):
    return (_f32(rng, m, din), _f32(rng, m, d), _f32(rng, din, 3 * d),
            _f32(rng, d, 3 * d), _f32(rng, 3 * d), _f32(rng, m, d),
            jnp.abs(_f32(rng, m)), jnp.float32(0.5))


def _memory_update_table_args(rng, n, m, d, din):
    # occurrence_order layout: node-grouped indices into the (N+2)-padded
    # table; the last occurrence of each group is the written one
    nodes = np.sort(rng.integers(0, n, size=m))
    last = np.r_[nodes[:-1] != nodes[1:], True]
    gidx = jnp.asarray(nodes, jnp.int32)
    widx = jnp.asarray(np.where(last, nodes, n), jnp.int32)
    return (_f32(rng, n, d), jnp.abs(_f32(rng, n)), _f32(rng, m, din),
            gidx, widx, jnp.abs(_f32(rng, m)), _f32(rng, din, 3 * d),
            _f32(rng, d, 3 * d), _f32(rng, 3 * d), _f32(rng, m, d),
            jnp.abs(_f32(rng, m)), jnp.float32(0.5))


def shape_plan(fast: bool = False):
    """(kernel, args, extra_kw) per representative shape. d_msg == d_mem at
    every call site, so Din == D throughout."""
    rng = _rng()
    # (occurrences, width) for the bench stream and the launch defaults
    sizes = [(200, 32)] if fast else [(200, 32), (1000, 100)]
    plan = []
    for m, d in sizes:
        plan.append(("gru_cell", (_f32(rng, m, d), _f32(rng, m, d),
                                  _f32(rng, d, 3 * d), _f32(rng, d, 3 * d),
                                  _f32(rng, 3 * d)), {}))
        plan.append(("pres_filter", (_f32(rng, m, d), _f32(rng, m, d),
                                     _f32(rng, m, d), jnp.abs(_f32(rng, m)),
                                     jnp.float32(0.5)), {}))
        plan.append(("memory_update", _memory_update_args(rng, m, d, d), {}))
    # whole-table Eq. 7 fill (pipeline staleness) at the bench-stream size
    plan.append(("pres_predict", (_f32(rng, 520, 32), _f32(rng, 520, 32),
                                  jnp.abs(_f32(rng, 520))), {}))
    plan.append(("memory_update_table",
                 _memory_update_table_args(rng, 520, 200, 32, 32), {}))
    # serve topk scoring at the batcher's default buckets x item catalogue
    for b in (16,) if fast else (16, 64):
        plan.append(("link_score", (_f32(rng, b, 32), _f32(rng, 120, 32),
                                    _f32(rng, 64, 32), _f32(rng, 32),
                                    _f32(rng, 32, 1), _f32(rng, 1)), {}))
    plan.append(("neighbor_attn",
                 (_f32(rng, 400, 32), _f32(rng, 400, 8, 32),
                  _f32(rng, 400, 8, 32),
                  jnp.asarray(_rng(1).random((400, 8)) < 0.7)), {}))
    return plan


def run(fast: bool = False, seeds: int = 1, force: bool = False):
    del seeds
    from repro.kernels import ops as kops
    backend = kops.backend()
    rows = []
    for name, args, extra_kw in shape_plan(fast):
        entry = dict(autotune.autotune(name, args, backend=backend,
                                       extra_kw=extra_kw or None,
                                       force=force))
        # a roofline floor exists only for a chip with published peaks
        ceiling = (roofline.kernel_ceiling_ms(
            name, args, jax.devices()[0].device_kind,
            extra_kw=extra_kw or None) if backend == "tpu" else None)
        if ceiling is not None:
            entry["ceiling_ms"] = round(ceiling, 6)
        autotune.record(backend, name, args, entry)
        rows.append({"kernel": name, "sig": autotune.shape_sig(args),
                     "mode": entry["mode"],
                     "blocks": str(entry.get("blocks", {})),
                     "ms": entry["ms"], "ceiling_ms": ceiling,
                     "swept": entry.get("swept", "")})
    common.emit("autotune_kernels", rows)
    print(f"\n[autotune] {len(rows)} entries -> "
          f"{autotune.cache_path(backend)}")
    return rows


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="bench-stream shapes only")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even when a cached entry exists")
    args = ap.parse_args(argv)
    run(fast=args.fast, force=args.force)


if __name__ == "__main__":
    main()
