"""Measure-once-then-cache autotuner for the kernel registry.

`ops.dispatch` resolves an execution mode and block sizes per call
(docs/KERNELS.md §Execution policy). When neither the caller nor the
`REPRO_KERNELS_MODE` env var pins a mode, dispatch consults this module's
persisted cache: per (backend, kernel, shape signature) the measured-fastest
candidate out of {compiled Pallas, interpret Pallas, jitted ref oracle} x
the registry's block-size grid. `benchmarks/autotune_kernels.py` is the CLI
that sweeps the shapes the model actually emits and persists the winners.

Cache file: results/autotune/<backend>.json —

    {
      "backend": "cpu",
      "jax": "0.4.37",
      "entries": {
        "memory_update|float32[200,32];float32[200,32];...": {
          "mode": "oracle", "blocks": {}, "ms": 0.21,
          "ceiling_ms": 0.05, "swept": 9
        }
      }
    }

The timer is injectable (tests select a deterministic winner with a fake
timer); the default measures wall clock to a `block_until_ready` sync,
best-of-`repeats` after one untimed compile call.
"""
from __future__ import annotations

import functools
import itertools
import json
import pathlib
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
             / "results" / "autotune")

# Bounded per-parameter sweep grids (the registry default is always
# included even if a shape rules the larger tiles out — dispatch pads).
BLOCK_CANDIDATES: dict[str, tuple[int, ...]] = {
    "block_m": (64, 128, 256, 512),
    "block_b": (16, 32, 64),
    "block_i": (64, 128, 256),
    # embed_attn: parent rows per grid step (R is padded to a multiple, so
    # every candidate is valid at every R)
    "block_r": (8, 16, 32, 64),
}


def shape_sig(args: Sequence) -> str:
    """Canonical dtype[shape] signature of a positional arg list — the
    cache key the model's call sites reproduce exactly."""
    parts = []
    for a in args:
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            dims = ",".join(str(int(s)) for s in a.shape)
            parts.append(f"{jnp.dtype(a.dtype).name}[{dims}]")
        else:
            parts.append(type(a).__name__)
    return ";".join(parts)


def cache_path(backend: str) -> pathlib.Path:
    return CACHE_DIR / f"{backend}.json"


@functools.lru_cache(maxsize=None)
def _file_entries(backend: str) -> dict:
    """Entries loaded ONCE per process (ops.reset_execution_policy or
    clear_cache drops the memo after a re-tune)."""
    p = cache_path(backend)
    if not p.exists():
        return {}
    try:
        return json.loads(p.read_text()).get("entries", {})
    except (json.JSONDecodeError, OSError):
        return {}


def clear_cache() -> None:
    _file_entries.cache_clear()


def n_entries(backend: str) -> int:
    return len(_file_entries(backend))


def lookup(backend: str, name: str, args: Sequence) -> dict | None:
    """Cached selection for this kernel at this shape, or None."""
    return _file_entries(backend).get(f"{name}|{shape_sig(args)}")


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def wall_timer(fn: Callable, args: Sequence, cand: dict,
               repeats: int = 3) -> float:
    """Default timer: one untimed call (compile), then best-of-`repeats`
    wall-clock ms to a block_until_ready sync. `cand` (the candidate being
    measured) is unused here but lets test timers pick winners
    deterministically."""
    del cand
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _block_grid(default_blocks: dict) -> list[dict]:
    if not default_blocks:
        return [{}]
    keys = sorted(default_blocks)
    axes = []
    for k in keys:
        cand = set(BLOCK_CANDIDATES.get(k, ()))
        cand.add(default_blocks[k])
        axes.append(sorted(cand))
    return [dict(zip(keys, combo)) for combo in itertools.product(*axes)]


def candidates(name: str, backend: str,
               modes: Sequence[str] | None = None) -> list[dict]:
    """The sweep: the jitted oracle (one candidate — block sizes do not
    apply) plus each Pallas mode crossed with the block grid. On CPU the
    compiled Pallas mode is excluded (Mosaic does not target CPU); on TPU
    the interpret mode is excluded (strictly dominated)."""
    from repro.kernels import ops
    spec = ops.get_kernel(name)
    if modes is None:
        modes = (("oracle", "compiled") if backend == "tpu"
                 else ("oracle", "interpret"))
    out = []
    for mode in modes:
        ops._check_mode(mode)
        if mode == "oracle":
            out.append({"mode": "oracle", "blocks": {}})
        else:
            out.extend({"mode": mode, "blocks": b}
                       for b in _block_grid(dict(spec.blocks)))
    return out


def tune(name: str, args: Sequence, *, backend: str | None = None,
         timer: Callable = wall_timer, modes: Sequence[str] | None = None,
         extra_kw: dict | None = None) -> dict:
    """Measure every candidate at these args and return the winning entry
    {"mode", "blocks", "ms", "swept"}. Candidates that fail to build (e.g.
    a tile larger than the padded shape supports) are skipped, except a
    compiled Pallas candidate on a TPU: that is the path the chip runs, so
    its failure raises with the kernel's name and the compiler's error
    instead of letting the oracle win unseen."""
    from repro.kernels import ops
    backend = backend or ops.backend()
    extra = dict(extra_kw or {})
    best, swept = None, 0
    for cand in candidates(name, backend, modes):
        fn = functools.partial(ops.dispatch, name, mode=cand["mode"],
                               **cand["blocks"], **extra)
        try:
            ms = float(timer(fn, args, cand))
        except Exception as e:
            if backend == "tpu" and cand["mode"] == "compiled":
                raise RuntimeError(
                    f"autotune: compiled Pallas kernel {name!r} (blocks "
                    f"{cand['blocks']}) failed on tpu at sig "
                    f"{shape_sig(args)}: {e}") from e
            continue
        swept += 1
        if best is None or ms < best["ms"]:
            best = {"mode": cand["mode"], "blocks": dict(cand["blocks"]),
                    "ms": ms}
    if best is None:
        raise RuntimeError(f"autotune: no candidate for kernel {name!r} "
                           f"succeeded at sig {shape_sig(args)}")
    best["swept"] = swept
    return best


def record(backend: str, name: str, args: Sequence, entry: dict) -> None:
    """Merge one winning entry into results/autotune/<backend>.json and
    invalidate the in-process memo so the next dispatch sees it."""
    p = cache_path(backend)
    p.parent.mkdir(parents=True, exist_ok=True)
    data = {"backend": backend, "jax": jax.__version__, "entries": {}}
    if p.exists():
        try:
            data = json.loads(p.read_text())
        except (json.JSONDecodeError, OSError):
            pass
    data["backend"] = backend
    data["jax"] = jax.__version__
    data.setdefault("entries", {})[f"{name}|{shape_sig(args)}"] = entry
    p.write_text(json.dumps(data, indent=2, sort_keys=True))
    clear_cache()


def autotune(name: str, args: Sequence, *, backend: str | None = None,
             timer: Callable = wall_timer, modes: Sequence[str] | None = None,
             extra_kw: dict | None = None, force: bool = False) -> dict:
    """Measure-once-then-cache: return the cached selection for this
    (kernel, shape) if present, otherwise tune, persist, and return it."""
    from repro.kernels import ops
    backend = backend or ops.backend()
    if not force:
        hit = lookup(backend, name, args)
        if hit is not None:
            return hit
    entry = tune(name, args, backend=backend, timer=timer, modes=modes,
                 extra_kw=extra_kw)
    record(backend, name, args, entry)
    return entry
