"""The program's host spans in a profiler trace: reading them, the device's
idle time inside them, and the span report of a whole run on the CPU."""
import json
import pathlib
import time
import types

import jax
import jax.numpy as jnp
import pytest

from bench import spans as span_tool
from bench.lib import cell, programtrace as pt, tracereduce
from repro.obs import trace as obs_trace

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = pathlib.Path(__file__).parent / "data"
CHIP = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_extract_reads_program_spans_beside_the_benchmarks(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    obs_trace.enable()
    try:
        with jax.profiler.TraceAnnotation("bench.run_epoch"):
            for i in range(2):
                with obs_trace.span("train.sample", step=i):
                    jnp.ones(4).block_until_ready()
                with obs_trace.span("serve.dispatch", kind="query", chunk=i,
                                    rows=3, bucket=16):
                    pass
    finally:
        obs_trace.disable()
        jax.profiler.stop_trace()
    prog = pt.extract(tmp_path)
    assert [(s["name"], s["args"]) for s in prog] == [
        ("repro.train.sample", {"step": 0}),
        ("repro.serve.dispatch", {"kind": "query", "chunk": 0, "rows": 3,
                                  "bucket": 16}),
        ("repro.train.sample", {"step": 1}),
        ("repro.serve.dispatch", {"kind": "query", "chunk": 1, "rows": 3,
                                  "bucket": 16})]
    # the benchmark's own reduction still keeps only its own spans
    host = tracereduce.extract(tmp_path)["host"]
    assert [h["name"] for h in host] == ["bench.run_epoch"]
    win = tracereduce.window({"host": host}, "bench.run_epoch")
    assert all(win[0] <= s["start_ns"] and
               s["start_ns"] + s["dur_ns"] <= win[1] for s in prog)


def test_idle_ns_in_hand_computed():
    small = json.loads((DATA / "trace_small.json").read_text())
    win = tracereduce.window(small, "bench.run_epoch")      # 1000 .. 11000
    ops = tracereduce.in_window(small["device"], win)
    # device 0 runs [1000, 3500], [5000, 7000], [10500, 11000]
    assert pt.gaps(ops, win) == [(3500, 5000), (7000, 10500)]

    def s(name, a, b):
        return {"name": name, "start_ns": a, "dur_ns": b - a, "args": {}}

    spans = [s("repro.train.sample", 3000, 4000),
             s("repro.train.pull", 4500, 8000),
             s("repro.train.pull", 6000, 7500),      # overlaps: counted once
             s("repro.train.dispatch", 9000, 12000)]
    assert pt.idle_ns_in(ops, win, spans, "repro.train.sample") == 500
    assert pt.idle_ns_in(ops, win, spans, "repro.train.pull") == 500 + 1000
    assert pt.idle_ns_in(ops, win, spans, "repro.train.dispatch") == 1500
    assert pt.idle_ns_in(ops, win, spans) == 3500
    assert pt.idle_ns_in(ops, win, spans, "repro.serve.pad") == 0
    # a device with nothing on it idles the whole window
    assert pt.gaps([], (0, 10)) == [(0, 10)]


def test_maintain_state_reader_on_small_extract():
    read = cell.metric_reader("stage_ms.train.maintain_state")
    ops = [{"device": 0, "module": "jit_train_step", "name": n,
            "start_ns": 0.0, "dur_ns": d}
           for n, d in [("fusion.1", 4e6), ("fusion.2", 2e6),
                        ("fusion.3", 9e6)]]
    scopes = {"jit_train_step": {
        "fusion.1": "jit(train_step)/maintain_state/jit(searchsorted)/while",
        "fusion.2": "jit(train_step)/maintain_state/scatter",
        "fusion.3": "jit(train_step)/jvp(embed)/dot_general"}}
    ctx = types.SimpleNamespace(ops=ops, scopes=scopes, steps=2, chips=1,
                                tr=tracereduce)
    assert read(ctx) == pytest.approx(3.0)         # (4 + 2) ms over 2 steps
    ctx.scopes = {}
    assert read(ctx) is None


def _cell(kind):
    name = {"train": "tgn-pres.train.wikipedia",
            "serve": "tgn-pres.serve.wikipedia"}[kind]
    spec = cell.workload(name)
    spec["traffic"] = cell.load_json(DATA / f"{kind}.tiny.json")
    return spec


def test_traced_training_run_has_one_span_of_each_stage_per_step(tmp_path):
    """A traced run on the CPU with the spans on: the traced epoch holds
    one sample, dispatch and pull span per step and one epoch end, the
    spans hold most of its (here wholly idle) window, and the spans are
    off again afterwards."""
    r = span_tool.run(_cell("train"), jax.devices(), 2**33 + 303, 0.3,
                      True, True, tmp_path / "trace",
                      time.perf_counter(), chip=CHIP)
    assert not obs_trace.enabled()
    steps = r["idle"]["per"]
    assert steps == 4
    n = r["idle"]["spans_in_window"]
    assert (n["repro.train.sample"], n["repro.train.dispatch"],
            n["repro.train.pull"], n["repro.train.epoch_end"]) == (
                steps, steps, steps, 1)
    assert r["idle"]["covered_share"] > 0.5
    assert set(r["idle"]["idle_ms_per"]) == {
        "repro.train.sample", "repro.train.dispatch", "repro.train.pull",
        "repro.train.epoch_end"}
    assert set(r["stage_ms"]) == {"memory_update", "embed", "maintain_state"}
    assert r["counts"] == {}
    assert r["host"]["summary"]["train.dispatch"]["count"] >= steps
    assert not (tmp_path / "trace").exists()


def test_traced_serving_run_reads_padding_and_request_kinds(tmp_path):
    r = span_tool.run(_cell("serve"), jax.devices(), 2**33 + 404, 0.3,
                      True, True, tmp_path / "trace",
                      time.perf_counter(), chip=CHIP)
    assert not obs_trace.enabled()
    idle, rounds = r["idle"], r["idle"]["per"]
    n = idle["spans_in_window"]
    assert n["repro.serve.dispatch[query]"] == rounds
    assert n["repro.serve.dispatch[ingest]"] == rounds
    assert n["repro.serve.pull[state]"] == rounds
    c = r["counts"]
    assert 0 < c["serve.rows_valid"] < c["serve.rows_sent"]
    assert r["pad_share"] == pytest.approx(
        100.0 * (1 - c["serve.rows_valid"] / c["serve.rows_sent"]))
    assert 0.0 < idle["idle_ms"] <= idle["window_idle_ms"]
