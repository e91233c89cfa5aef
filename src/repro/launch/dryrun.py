import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first initialisation). 512 placeholder host devices back the
# production meshes: (16,16)=256 single-pod, (2,16,16)=512 multi-pod.

import argparse      # noqa: E402
import json          # noqa: E402
import pathlib       # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable  # noqa: E402
from repro.launch import mesh as mesh_lib                                 # noqa: E402
from repro.launch import specs as specs_lib                               # noqa: E402

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
# bytes-on-the-wire factor per collective kind (ring algorithms):
#   all-reduce moves ~2x the buffer; others ~1x.
WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}


def _lhs_bytes(line: str) -> int:
    """Sum the byte sizes of every type[dims] on the LHS of an HLO line."""
    lhs = line.split(" = ", 1)[0] if " = " in line else ""
    # result types actually appear after '=': "%x = bf16[2,3]{1,0} all-gather(".
    rhs = line.split(" = ", 1)[1] if " = " in line else line
    opm = COLLECTIVE_RE.search(rhs)
    if not opm:
        return 0
    head = rhs[: opm.start()]
    total = 0
    for m in SHAPE_RE.finditer(head):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str, loop_trip: int = 1) -> dict:
    """Per-device collective bytes from the post-SPMD optimized HLO.

    XLA emits scan loops as while-ops whose body computation appears ONCE in
    the text but executes `loop_trip` times (the scan-over-layers trip
    count). Ops inside loop-body computations (name contains "region") are
    therefore multiplied by loop_trip — without this the collective term of
    every scanned model is under-reported by ~n_layers."""
    stats = {k: {"count": 0, "bytes": 0.0} for k in WIRE_FACTOR}
    in_body = False
    for line in hlo_text.splitlines():
        if not line.startswith(" "):       # computation header line
            head = line.split(" ")[0]
            in_body = "region" in head
        m = COLLECTIVE_RE.search(line)
        if not m or "-done(" in line:
            continue
        kind = m.group(1)
        mult = loop_trip if in_body else 1
        b = _lhs_bytes(line)
        stats[kind]["count"] += mult
        stats[kind]["bytes"] += b * WIRE_FACTOR[kind] * mult
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    return stats


def scan_trip_count(cfg) -> int:
    """Scan-over-layers trip count per architecture (the multiplier for
    loop-body collectives)."""
    if type(cfg).__name__ == "MDGNNConfig":
        return 1
    if not getattr(cfg, "scan_layers", False):
        return 1
    if cfg.family == "audio":
        return max(cfg.n_layers, cfg.enc_layers)
    if cfg.family in ("dense", "vlm"):
        pattern = cfg.global_every if cfg.global_every else 1
        return cfg.n_layers // pattern
    if cfg.family == "moe":
        return cfg.n_layers - cfg.first_dense
    if cfg.family == "ssm":
        pattern = cfg.slstm_every if cfg.slstm_every else 1
        return cfg.n_layers // pattern
    if cfg.family == "hybrid":
        pattern = cfg.attn_every if cfg.attn_every else 1
        return cfg.n_layers // pattern
    return cfg.n_layers


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) for training;
    2*N*D for a forward-only shape; per decode step D = global_batch tokens."""
    if type(cfg).__name__ == "MDGNNConfig":
        import jax.numpy as jnp  # noqa
        from repro.models import mdgnn as mdgnn_lib
        shapes = jax.eval_shape(
            lambda k: mdgnn_lib.init_params(k, cfg)[0], jax.random.PRNGKey(0))
        n_params = sum(int(jnp_size(leaf)) for leaf in jax.tree.leaves(shapes))
        events = shape.global_batch * shape.seq_len
        return 6.0 * n_params * events
    n_params = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_params * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_params * tokens
    return 2.0 * n_params * shape.global_batch  # one token per sequence


def jnp_size(leaf) -> int:
    n = 1
    for d in leaf.shape:
        n *= d
    return n


def active_param_count(cfg) -> float:
    """Active parameters per token (MoE counts top_k + shared + dense)."""
    from repro.launch.specs import abstract_init
    from repro.archs.api import get_model
    shapes, _ = abstract_init(get_model(cfg))
    total = 0
    moe_total = 0
    import jax.tree_util as jtu
    for path, leaf in jtu.tree_leaves_with_path(shapes):
        keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        size = 1
        for d in leaf.shape:
            size *= d
        if "/moe/w" in keys:   # expert weights: only top_k/E are active
            moe_total += size
        else:
            total += size
    if cfg.n_experts:
        total += moe_total * cfg.top_k / cfg.n_experts
    return float(total)


def run_pair(arch_id: str, shape_name: str, multi_pod: bool,
             rules: str | None = None, optimizer: str | None = None,
             strategy: str = "gspmd", dense_attn: bool = False) -> dict:
    shape = SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rule_dict = (None if rules is None
                 else dict(specs_lib.module_lib.RULE_SETS[rules]))
    if arch_id == "tgn-pres":
        # The paper's own workload: a temporal batch of global_batch*seq_len
        # events against the production-scale sharded memory table.
        import dataclasses as _dc
        from repro.configs.tgn_pres import PRODUCTION
        from repro.train.distributed import make_mdgnn_train_spec
        cfg = PRODUCTION
        if strategy == "optimized":
            # beyond-paper bundle (EXPERIMENTS.md §Perf): replicated params +
            # 256-way event parallelism + replicated state + bucketed
            # (Sec. 5.3) PRES trackers + bf16 memory table
            cfg = _dc.replace(cfg, pres_buckets=65536, mem_dtype="bfloat16")
            rule_dict = rule_dict or dict(
                specs_lib.module_lib.RULE_SETS["mdgnn_event_dp_repl"])
        spec = make_mdgnn_train_spec(cfg, shape.global_batch * shape.seq_len,
                                     mesh, rules=rule_dict,
                                     strategy=strategy)
    else:
        cfg = get_config(arch_id)
        if dense_attn:   # paper-era dense attention (perf baseline)
            import dataclasses as _dc
            cfg = _dc.replace(cfg, attn_chunk=None)
        spec = specs_lib.make_spec(cfg, shape, mesh, rules=rule_dict,
                                   optimizer=optimizer)
    t0 = time.perf_counter()
    with mesh:
        jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings,
                         donate_argnums=spec.donate_argnums)
        lowered = jitted.lower(*spec.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover - backend-specific
        mem_info = {"error": str(e)}
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    trip = scan_trip_count(cfg)
    coll = collective_stats(compiled.as_text(), loop_trip=trip)
    chips = mesh.devices.size
    mf = model_flops(cfg, shape)
    result = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll["total_bytes"],
        "scan_trip": trip,
        "collectives": {k: v for k, v in coll.items() if isinstance(v, dict)},
        "memory_analysis": mem_info,
        "model_flops_global": mf,
        "status": "ok",
    }
    # roofline terms (seconds) — single-program = per-device quantities.
    # CAVEAT: XLA cost_analysis counts a while-loop body ONCE, so scanned
    # layer stacks under-report HLO flops/bytes by ~n_layers; the analytic
    # MODEL_FLOPS floor (6ND/2ND per chip) corrects the compute term.
    peaks = mesh_lib.chip_peaks(mesh_lib.DRY_RUN_KIND)
    result["compute_hlo_s"] = flops / peaks["flops_bf16"]
    result["compute_model_s"] = (mf / chips) / peaks["flops_bf16"]
    result["compute_s"] = max(result["compute_hlo_s"],
                              result["compute_model_s"])
    result["memory_s"] = bytes_accessed / peaks["hbm_bytes_per_s"]
    result["collective_s"] = (coll["total_bytes"]
                              / peaks["ici_bytes_per_s_per_link"])
    terms = {"compute": result["compute_s"], "memory": result["memory_s"],
             "collective": result["collective_s"]}
    result["bottleneck"] = max(terms, key=terms.get)
    result["useful_flops_ratio"] = (mf / chips) / flops if flops else None
    return result


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--rules", default=None,
                    help="override logical->mesh rule set (hillclimbing)")
    ap.add_argument("--optimizer", default=None)
    ap.add_argument("--strategy", default="gspmd",
                    help="MDGNN distribution strategy: gspmd | compact_update"
                         " | optimized")
    ap.add_argument("--dense-attn", action="store_true",
                    help="disable blockwise attention (dense baseline)")
    ap.add_argument("--tag", default=None, help="suffix for result filenames")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"-{args.tag}" if args.tag else ""
                name = f"{arch}__{shape}__{mesh_kind}{tag}.json"
                path = outdir / name
                if args.skip_existing and path.exists():
                    print(f"[skip existing] {name}")
                    continue
                if not shape_applicable(arch, shape):
                    path.write_text(json.dumps({
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "status": "skipped",
                        "reason": "long_500k requires sub-quadratic attention "
                                  "(see DESIGN.md)"}, indent=2))
                    print(f"[skip n/a] {name}")
                    continue
                print(f"[dryrun] {arch} x {shape} x {mesh_kind} ...", flush=True)
                try:
                    res = run_pair(arch, shape, mesh_kind == "multi",
                                   rules=args.rules, optimizer=args.optimizer,
                                   strategy=args.strategy,
                                   dense_attn=args.dense_attn)
                except Exception as e:
                    res = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "error", "error": str(e),
                           "traceback": traceback.format_exc()}
                path.write_text(json.dumps(res, indent=2))
                status = res["status"]
                extra = ""
                if status == "ok":
                    extra = (f" compile={res['compile_s']}s "
                             f"bottleneck={res['bottleneck']} "
                             f"C={res['compute_s']:.4f}s M={res['memory_s']:.4f}s "
                             f"X={res['collective_s']:.4f}s")
                print(f"[done] {name}: {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
