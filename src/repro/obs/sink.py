"""JSONL run-log sink + run manifests (docs/OBSERVABILITY.md §Sink).

One schema shared by training and serving: a run-log is a JSONL file
whose first record is the run manifest (`kind: "manifest"` — provenance,
config, obs-field schema) followed by per-epoch / per-replay records and
a closing block the sink writes itself (host spans and counts, the
kernel-dispatch table from `kernels/ops.py`, and an `end` marker).
`benchmarks/common.run_metadata` delegates to `run_metadata` here, so
committed benchmark JSONs and run-logs carry the same provenance fields —
including the git commit and a config digest, which make a number
traceable to a revision.

`tools/inspect_run.py` renders a run-log; `canonical()` strips the
wall-clock-dependent fields so two runs of the same seed compare equal
(the deterministic-log test contract).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import pathlib
import subprocess
import sys
import time

SCHEMA_VERSION = 1

# Fields whose values depend on wall clock / machine load, stripped by
# canonical() so deterministic runs produce byte-equal canonical logs.
NONDET_KEYS = frozenset({
    "t_start", "t_end", "seconds", "dur_s", "t0", "events_per_sec",
    "queries_per_sec", "epoch_seconds", "compile_seconds", "sim_rate",
    "ingest_ms", "query_ms", "wall_s",
})

# Record kinds wholly made of timing (dropped by canonical()).
_NONDET_KINDS = frozenset({"spans", "end"})


@functools.lru_cache(maxsize=1)
def git_commit() -> str | None:
    """Current git commit hash (None outside a repo / without git)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5, cwd=pathlib.Path(__file__).resolve().parent)
    except (OSError, subprocess.TimeoutExpired):
        return None
    return out.stdout.strip() if out.returncode == 0 else None


def cfg_digest(cfg) -> str:
    """Short stable digest of a config (dataclass or dict): sha256 over
    the sorted-key JSON of its fields. Two runs with equal digests ran
    the same model/schedule configuration."""
    if dataclasses.is_dataclass(cfg) and not isinstance(cfg, type):
        cfg = dataclasses.asdict(cfg)
    blob = json.dumps(cfg, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def run_metadata(cfg=None) -> dict:
    """Provenance stamped into every run-log manifest and benchmark JSON:
    without the jax version, backend, kernel execution mode and git
    commit, a committed number cannot be compared against a re-run
    (docs/KERNELS.md §Execution policy)."""
    import jax
    import jaxlib
    from repro.kernels import ops as kops
    pol = kops.execution_policy()
    meta = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": pol["backend"],
        "kernels_default_mode": pol["default_mode"],
        "kernels_env_mode": pol["env_mode"],
        "autotune_entries": pol["autotune_entries"],
        "device_count": jax.device_count(),
        "cpu_count": __import__("os").cpu_count(),
        "git_commit": git_commit(),
    }
    if cfg is not None:
        meta["cfg_digest"] = cfg_digest(cfg)
    return meta


class RunLog:
    """Append-only JSONL run-log with a leading manifest record.

    The sink never touches device values — callers hand it host scalars /
    lists (the engines' one-fetch-per-epoch flush), so writing a record
    costs a json.dumps and a line append, off the step path entirely.
    `close()` appends the telemetry epilogue: recorded host spans and
    counter totals (obs.trace), the kernel-dispatch table (which
    execution-policy branch each registered kernel actually took), and an
    `end` marker."""

    def __init__(self, path, *, role: str, cfg=None, argv=None,
                 extra: dict | None = None):
        self.path = pathlib.Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._f = open(self.path, "w")
        self._closed = False
        manifest = {
            "schema_version": SCHEMA_VERSION,
            "role": role,
            "meta": run_metadata(cfg),
            "argv": list(argv if argv is not None else sys.argv[1:]),
            "obs_fields": _obs_fields(),
            "t_start": time.time(),
        }
        if cfg is not None:
            c = (dataclasses.asdict(cfg)
                 if dataclasses.is_dataclass(cfg) else dict(cfg))
            manifest["cfg"] = {k: _jsonable(v) for k, v in c.items()}
        if extra:
            manifest.update(extra)
        self.write("manifest", **manifest)

    def write(self, kind: str, **payload) -> None:
        if self._closed:
            raise ValueError(f"run-log {self.path} is closed")
        rec = {"kind": kind, **{k: _jsonable(v) for k, v in payload.items()}}
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()

    def close(self) -> None:
        if self._closed:
            return
        from repro.kernels import ops as kops
        from repro.obs import trace as obs_trace
        spans = obs_trace.drain()
        if spans:
            self.write("spans", summary=obs_trace.span_summary(spans),
                       spans=spans)
        counts = obs_trace.drain_counts()
        if counts:
            self.write("counts", counts=counts)
        table = kops.dispatch_log()
        if table:
            self.write("kernel_dispatch", table=table)
        self.write("end", t_end=time.time())
        self._f.close()
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _obs_fields():
    from repro.obs import metrics as obs_metrics
    return list(obs_metrics.TRAIN_OBS_FIELDS)


def _jsonable(v):
    """Host-side JSON coercion for numpy scalars/arrays and nested trees."""
    import numpy as np
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, np.ndarray):
        return [_jsonable(x) for x in v.tolist()]
    if isinstance(v, np.generic):
        return v.item()
    return v


def read_runlog(path) -> list[dict]:
    """Parse a run-log; raises ValueError on a malformed file or a
    missing/foreign manifest (the inspector's entry contract)."""
    records = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSONL ({e})") from None
    if not records or records[0].get("kind") != "manifest":
        raise ValueError(f"{path}: first record must be a run manifest")
    if records[0].get("schema_version") != SCHEMA_VERSION:
        raise ValueError(
            f"{path}: schema_version {records[0].get('schema_version')!r} "
            f"(this reader speaks {SCHEMA_VERSION})")
    return records


def canonical(records: list[dict]) -> list[dict]:
    """Strip wall-clock-dependent fields (NONDET_KEYS, span/end records)
    so two runs of the same seeded computation compare equal."""
    def strip(v):
        if isinstance(v, dict):
            return {k: strip(x) for k, x in v.items() if k not in NONDET_KEYS}
        if isinstance(v, list):
            return [strip(x) for x in v]
        return v

    return [strip(r) for r in records
            if r.get("kind") not in _NONDET_KINDS]
