"""Training cells: the program's sequential engine with its Pallas kernels,
driven through `pipeline.make_train_step` and `pipeline.run_epoch` the way
`launch/train.py`'s epoch loop drives them.

The program path is set here and nowhere else (`PROGRAM_PATH`). One run:

1. set-up: the stream from the seed, the weights on the device, the
   compiled step; the step takes its first `check_steps` steps through
   `run_epoch` (which warms every shape the window uses) while the
   harness keeps what the comparison needs;
2. the window: whole epochs back to back, each over every batch, until
   `seconds` have passed; the rate is the real events trained over the
   window's time;
3. with `trace`, a profiler trace of one further epoch;
4. the check: the program's state is freed, the reference takes the same
   first steps from the same weights, events and negatives, and
   `compare.training_numbers` holds the two against the cell's limits.
"""
from __future__ import annotations

import dataclasses
import gc
import re
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import cell, compare, device, reference, streams

# the program path every training cell measures
PROGRAM_PATH = dict(use_kernels=True, kernels_mode="auto", pipeline_depth=0,
                    scan_chunk=1, dedup_embed=True, n_shards=1)


def seed_key(seed: int):
    """A PRNG key from any whole number (PRNGKey alone keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def make_stream(traffic: dict, seed: int):
    g = traffic["graph"]
    return streams.stream(g["n_users"], g["n_items"], g["n_events"],
                          g["feat_dim"], seed, exponent=g["exponent"],
                          noise=g["noise"], dt=g["dt"])


def model_spec(config: dict, traffic: dict) -> dict:
    """The configuration as the reference reads it, with the graph's node
    count."""
    g = traffic["graph"]
    return {"model": config["model"], "optimizer": config["optimizer"],
            "n_nodes": g["n_users"] + g["n_items"]}


def program_config(config: dict, traffic: dict):
    """The program's `MDGNNConfig`: every key of the configuration's
    `model`, the graph's sizes and `PROGRAM_PATH`. A model key the program
    has no field for is refused, unless the configuration's module lists
    it in `NOT_TAKEN` at the value the configuration gives, so that no
    configuration sets a width the program would silently ignore."""
    from repro.models.mdgnn import MDGNNConfig
    m, g = config["model"], traffic["graph"]
    not_taken = cell.config_module(config["name"]).NOT_TAKEN
    fields = {f.name for f in dataclasses.fields(MDGNNConfig)}
    harness = set(PROGRAM_PATH) | {"n_nodes", "d_edge"}
    for k, v in m.items():
        if k in harness:
            raise ValueError(f"model key {k!r} is set by the harness, not "
                             "by a configuration")
        if k not in fields and (k not in not_taken or not_taken[k] != v):
            raise ValueError(
                f"the program has no field for model key {k!r} = {v!r}; "
                f"NOT_TAKEN allows {not_taken}")
    return MDGNNConfig(n_nodes=g["n_users"] + g["n_items"],
                       d_edge=g["feat_dim"],
                       **{k: v for k, v in m.items() if k in fields},
                       **PROGRAM_PATH)


def extra_tables(arch, m: dict) -> tuple:
    """The names of the tables the configuration's module adds to the node
    state: paths into the program's state tree, as `group/key`."""
    return tuple(jax.eval_shape(lambda: arch.extra_state(1, m, jnp.float32)))


def program_state(state, extra=()) -> dict:
    """The program's node state under the reference's table names: the
    shared tables, and each of the module's `extra` tables by its path."""
    out = {"mem": state["memory"].mem,
           "last_update": state["memory"].last_update,
           "nbr": state["neighbors"]["nbr"], "nbr_t": state["neighbors"]["t"],
           "ptr": state["neighbors"]["ptr"], "pres_n": state["pres"].n,
           "pres_xi": state["pres"].xi, "pres_psi": state["pres"].psi}
    for name in extra:
        table = state
        for part in name.split("/"):
            table = table[part]
        out[name] = table
    return out


class Recorder:
    """The step the harness hands to `run_epoch`: the program's step in a
    `TraceAnnotation`, keeping what the check needs from the first calls."""

    def __init__(self, step, n_check: int, b1: float, extra=()):
        self.step, self.n_check, self.b1 = step, n_check, b1
        self.extra = extra
        self.calls = 0
        self.shapes = None
        self.losses, self.grads, self.params_end, self.state_end = \
            [], None, None, None

    def __call__(self, params, opt_state, state, *batches):
        i = self.calls
        self.calls += 1
        if i == 0:
            self.shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (params, opt_state, state) + batches)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            out = self.step(params, opt_state, state, *batches)
        if i < self.n_check:
            params2, opt2, state2, m = out
            self.losses.append(m["loss"])
            if i == 0:   # Adam's first moment after one step is (1 - b1) g
                self.grads = jax.tree.map(lambda m_: m_ / (1.0 - self.b1),
                                          jax.device_get(opt2["mu"]))
            if i == self.n_check - 1:
                self.params_end = jax.device_get(params2)
                self.state_end = jax.device_get(
                    program_state(state2, self.extra))
        return out

    def scopes(self) -> dict:
        """{module: {instruction: name stack}} of the compiled step, read
        from its HLO text (the compile is a cache hit: the same program)."""
        from bench.lib import tracereduce
        if not hasattr(self.step, "lower"):
            return {}
        text = self.step.lower(*self.shapes).compile().as_text()
        name = re.search(r"^HloModule ([^ ,]+)", text, re.M).group(1)
        return {name: tracereduce.hlo_scopes(text)}


def run_cell(config: dict, traffic: dict, limits: dict, seed: int,
             seconds: float, trace: bool, devs, t_start: float,
             trace_dir=None, fault=None) -> dict:
    """One run of a training cell. `fault(cfg, step) -> step` plants a
    fault under the harness (the benchmark's own tests)."""
    from repro.kernels import ops as kops
    from repro.optim import adamw
    from repro.train import pipeline
    from repro.graph.events import EventStream
    from repro.models import mdgnn

    counter = device.compile_counter()
    arch = cell.config_module(config["name"])
    g = traffic["graph"]
    bsz = traffic["batch_size"]
    n_check = traffic["check_steps"]
    opt_cfg = config["optimizer"]
    cfg = program_config(config, traffic)
    dst_range = (g["n_users"], g["n_users"] + g["n_items"])
    key = seed_key(seed)

    # ---------------------------------------------------------- set-up --
    phases = {"start": time.perf_counter() - t_start}
    with jax.profiler.TraceAnnotation("bench.setup"):
        stream = make_stream(traffic, seed)
        phases["stream"] = time.perf_counter() - t_start
        params = reference.init_params(arch, jax.random.fold_in(key, 0),
                                       config["model"], g["feat_dim"])
        want = jax.eval_shape(lambda k: mdgnn.init_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
        got = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                           params)
        if want != got:
            raise RuntimeError("the program's weights no longer have the "
                               f"reference's layout: {want} vs {got}")
        params0 = jax.device_get(params)
        opt = adamw(opt_cfg["lr"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
                    eps=opt_cfg["eps"])
        opt_state = opt.init(params)
        state = mdgnn.init_state(cfg)
        step = pipeline.make_train_step(cfg, opt)
        if fault is not None:
            step = fault(cfg, step)
        batches = EventStream(*stream, cfg.n_nodes).temporal_batches(bsz)
        phases["batches"] = time.perf_counter() - t_start
        events_per_epoch = int(sum(min(bsz, len(stream[0]) - i * bsz)
                                   for i in range(1, len(batches))))
        steps_per_epoch = len(batches) - 1
        kops.reset_dispatch_log()
        rec = Recorder(step, n_check, opt_cfg["b1"],
                       extra_tables(arch, config["model"]))
        params, opt_state, state, _ = pipeline.run_epoch(
            params, opt_state, state, batches[:n_check + 1], cfg, rec,
            jax.random.fold_in(key, 1), dst_range)
        jax.block_until_ready((params, opt_state, state))
        gc.collect()
    setup_s = time.perf_counter() - t_start
    phases["first_steps"] = setup_s
    phases["compiling"] = counter.seconds

    # ---------------------------------------------------------- window --
    counter.active = True
    epoch = 0

    def one_epoch(p, o, s):
        nonlocal epoch
        with jax.profiler.TraceAnnotation("bench.run_epoch"):
            p, o, s, res = pipeline.run_epoch(
                p, o, s, batches, cfg, rec, jax.random.fold_in(key, 2 + epoch),
                dst_range)
        epoch += 1
        return p, o, s, res

    failed_steps, steps, events, elapsed = 0, 0, 0, 0.0
    t0 = time.perf_counter()
    while seconds > 0:
        params, opt_state, state, res = one_epoch(params, opt_state, state)
        steps += steps_per_epoch
        events += events_per_epoch
        if not np.isfinite(res.loss):
            failed_steps += steps_per_epoch
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    counter.active = False
    compiles_in_window = counter.count
    metrics = {"train_events_per_s": {"value": events / max(elapsed, 1e-9),
                                      "unit": "events/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}
    traced = None
    if trace:
        counter.active = True
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=device.profile_options())
        params, opt_state, state, res = one_epoch(params, opt_state, state)
        jax.block_until_ready((params, opt_state, state))
        jax.profiler.stop_trace()
        counter.active = False
        compiles_in_window = counter.count
        written = [len(np.unique(np.concatenate(
            [s_[i * bsz:(i + 1) * bsz] for s_ in stream[:2]])))
            for i in range(len(batches) - 1)]
        traced = {"dir": trace_dir, "span": "bench.run_epoch",
                  "steps": steps_per_epoch,
                  "steps_per_s": steps / elapsed,
                  "written_per_step": float(np.mean(written)),
                  "scopes": rec.scopes()}
    peak = device.memory_peak_bytes(devs)
    dispatch = kops.dispatch_log()

    # ----------------------------------------------------------- check --
    losses = [float(x) for x in rec.losses]
    prog = {"losses": losses, "grads": rec.grads, "params0": params0,
            "params_end": rec.params_end, "state_end": rec.state_end}
    del params, opt_state, state, batches, rec, step
    gc.collect()
    ref_steps, ref_params, ref_state = reference.run(
        arch, model_spec(config, traffic), params0, stream, bsz, dst_range,
        jax.random.fold_in(key, 1), n_check)
    ref = {"losses": [r["loss"] for r in ref_steps],
           "grads": ref_steps[0]["grads"], "params0": params0,
           "params_end": ref_params, "state_end": ref_state}
    numbers, where = compare.training_numbers(prog, ref, arch.EXACT)
    correct, checks = compare.judge(numbers, limits["limits"])
    not_compiled = {k: v for k, v in dispatch.items()
                    if set(v) != {"compiled"}}
    return {
        "numbers": numbers, "ref": ref, "setup_phases": phases,
        "correct": bool(correct and not not_compiled
                        and compiles_in_window == 0 and failed_steps == 0),
        "attempted": steps, "failed": failed_steps,
        "metrics": metrics, "peak": peak, "traced": traced,
        "checks": checks, "where": where, "dispatch": dispatch,
        "not_compiled": not_compiled,
        "compiles_in_window": compiles_in_window,
        "losses": losses, "ref_losses": ref["losses"],
    }
