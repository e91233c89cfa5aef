"""§Roofline: consolidate the dry-run JSONs into the roofline table —
compute/memory/collective terms (seconds), dominant bottleneck, and the
MODEL_FLOPS / HLO_FLOPs usefulness ratio, per (arch x shape x mesh)."""
from __future__ import annotations

import json
import pathlib

from benchmarks import common
from repro.launch.mesh import DRY_RUN_KIND, chip_peaks

DRYRUN_DIR = pathlib.Path(__file__).resolve().parent.parent / "results" / "dryrun"

# One-line "what moves the dominant term down" per (bottleneck, shape kind).
LEVERS = {
    ("collective", "train"): "overlap grad all-reduce with bwd; bf16 "
        "activation ARs; sequence-sharding between blocks",
    ("collective", "prefill"): "weight-stationary scheduling / bigger "
        "per-chip batch to amortize weight+expert traffic",
    ("collective", "decode"): "multi-token (speculative) decode or weight "
        "caching — 1 token cannot amortize gathers",
    ("memory", "train"): "more aggressive remat policy; fuse "
        "norm+matmul epilogues; bf16 master-weight reads",
    ("memory", "prefill"): "larger attention chunks (more reuse per HBM "
        "read); fuse QKV projections",
    ("memory", "decode"): "quantize KV cache (int8); batch more sequences "
        "per chip",
    ("compute", "train"): "already compute-bound — raise MFU via larger "
        "matmul tiles / fewer remat recomputes",
    ("compute", "prefill"): "already compute-bound — good",
    ("compute", "decode"): "already compute-bound — good",
}


def kernel_ceiling_ms(name: str, args, device_kind: str,
                      extra_kw: dict | None = None) -> float:
    """Memory-roofline floor (ms) for one registry kernel at these args:
    every input read once + every output written once at the chip's
    published HBM bandwidth. Output shapes come from jax.eval_shape of the
    kernel's oracle, so no computation runs. benchmarks/autotune_kernels.py
    stamps this next to each winner measured on a chip."""
    import functools

    import jax

    from repro.kernels import ops as kops
    bw = chip_peaks(device_kind)["hbm_bytes_per_s"]
    spec = kops.get_kernel(name)
    fn = functools.partial(spec.oracle or spec.ref, **(extra_kw or {}))
    outs = jax.eval_shape(fn, *args)
    arrays = [a for a in list(args) + jax.tree.leaves(outs)
              if hasattr(a, "shape") and hasattr(a, "dtype")]
    nbytes = sum(int(a.size) * a.dtype.itemsize for a in arrays)
    return nbytes / bw * 1e3


def _kind(shape_name: str) -> str:
    return {"train_4k": "train", "prefill_32k": "prefill"}.get(
        shape_name, "decode")


def load_all(tag: str | None = None):
    out = []
    for p in sorted(DRYRUN_DIR.glob("*.json")):
        stem = p.stem
        has_tag = "-" in stem.split("__")[-1]
        if tag is None and has_tag:
            continue
        if tag is not None and not stem.endswith(f"-{tag}"):
            continue
        out.append(json.loads(p.read_text()))
    return out


def run(fast: bool = False, seeds: int = 1):
    rows = []
    for d in load_all():
        def _stub(status):
            return {"arch": d["arch"], "shape": d["shape"],
                    "mesh": d["mesh"], "compute_s": "",
                    "compute_hlo_s": "", "memory_s": "",
                    "collective_s": "", "bottleneck": status,
                    "useful_flops_ratio": "", "hbm_bytes_per_device": "",
                    "lever": ""}

        if d.get("status") == "skipped":
            rows.append(_stub("skipped"))
            continue
        if d.get("status") != "ok":
            rows.append(_stub("ERROR"))
            continue
        mem = d.get("memory_analysis", {})
        hbm = (mem.get("argument_bytes") or 0) + (mem.get("temp_bytes") or 0)
        # analytic compute floor: XLA cost_analysis counts while-loop bodies
        # once, so scanned layer stacks under-report flops by ~n_layers;
        # MODEL_FLOPS/chips/peak corrects the compute term.
        c_model = (d.get("model_flops_global", 0.0) / d["chips"]
                   / chip_peaks(DRY_RUN_KIND)["flops_bf16"])
        c = max(d["compute_s"], c_model)
        terms = {"compute": c, "memory": d["memory_s"],
                 "collective": d["collective_s"]}
        bt = max(terms, key=terms.get)
        rows.append({
            "arch": d["arch"], "shape": d["shape"], "mesh": d["mesh"],
            "compute_s": c, "compute_hlo_s": d["compute_s"],
            "memory_s": d["memory_s"],
            "collective_s": d["collective_s"],
            "bottleneck": bt,
            "useful_flops_ratio": d.get("useful_flops_ratio") or "",
            "hbm_bytes_per_device": hbm,
            "lever": LEVERS.get((bt, _kind(d["shape"])), ""),
        })
    common.emit("roofline", rows)
    return rows
