"""The harness finds every part of a cell by name, and refuses to run
without a chip."""
import json
import re
import os
import pathlib
import subprocess
import sys
import types

import pytest

from bench.lib import cell, flops, tracereduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = [c["name"] for c in SPEC["configs"]]


def _first_cell(config: str) -> dict:
    """The first cell of a configuration, for the widths it runs at."""
    name = next(w["name"] for w in SPEC["workloads"] if w["config"] == config)
    return cell.workload(name, SPEC)


@pytest.mark.parametrize("config", CONFIGS)
def test_config_module_loads(config):
    mod = cell.config_module(config)
    assert all(hasattr(mod, n) for n in cell.MODULE_NAMES)
    assert cell.config_module(config) is mod     # loaded once
    assert _first_cell(config)["module"] is mod


@pytest.mark.parametrize("config", CONFIGS)
def test_emb_shapes_are_the_programs(config):
    import jax
    from bench.lib import train
    from repro.models import mdgnn
    w = _first_cell(config)
    cfg = train.program_config(w["config"], w["traffic"])
    want = jax.eval_shape(lambda k: mdgnn.init_params(k, cfg)[0]["emb"],
                          jax.random.PRNGKey(0))
    got = w["module"].emb_shapes(w["config"]["model"],
                                 w["traffic"]["graph"]["feat_dim"])
    assert jax.tree.map(lambda a: tuple(a.shape), want) == got


@pytest.mark.parametrize("config", CONFIGS)
def test_every_model_key_reaches_the_program(config):
    """A model key is a field of the program's config, or the module names
    it in NOT_TAKEN at the value the configuration gives."""
    import dataclasses
    from repro.models.mdgnn import MDGNNConfig
    fields = {f.name for f in dataclasses.fields(MDGNNConfig)}
    w = _first_cell(config)
    not_taken = w["module"].NOT_TAKEN
    for k, v in w["config"]["model"].items():
        assert k in fields or (k in not_taken and not_taken[k] == v), k


def test_train_step_flops_of_the_wikipedia_cell():
    """The FLOP count behind `mfu.train` in tgn-pres.train.wikipedia, as the
    shared count gave it before the embedding's moved to the module."""
    w = cell.workload("tgn-pres.train.wikipedia", SPEC)
    assert flops.train_step_flops(
        w["config"]["model"], w["traffic"]["graph"]["feat_dim"],
        w["traffic"]["batch_size"], w["module"]) == 12015600000.0


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_has_its_files(name):
    w = cell.workload(name, SPEC)
    assert w["config"]["name"] == w["entry"]["config"]
    names = {"train": {"loss_gap", "first_loss_gap", "grad_norm_gap",
                       "grad_norm_gap.median", "update_norm_gap",
                       "update_norm_gap.median", "state_gap",
                       "state_mismatch"},
             "serve": {"score_gap", "topk_rank_gap", "topk_score_gap",
                       "state_gap", "state_mismatch"}}[w["traffic"]["kind"]]
    assert w["limits"]["limits"] and set(w["limits"]["limits"]) <= names
    assert any(m["name"] == "setup_s" for m in w["end_to_end"])
    assert len(w["end_to_end"]) >= 2 and w["per_layer"]


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_every_metric_reader_reads_nothing_from_an_empty_trace(name):
    read = cell.metric_reader(name)
    ctx = types.SimpleNamespace(
        ops=[], scopes={}, win=(0.0, 1.0), window_ns=1.0, steps=1, steps_per_s=0.0,
        written_per_step=1.0, chips=1, calls={"query": 1, "ingest": 1},
        model=json.loads((ROOT / "bench/configs/tgn-pres.json").read_text()
                         )["model"],
        traffic=json.loads((ROOT / "bench/workloads/train.wikipedia.json"
                            ).read_text()),
        peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
        arch=cell.config_module("tgn-pres"), tr=tracereduce, flops=flops)
    value = read(ctx)
    # nothing traced: a share of a roofline or a stage time is absent, not 0
    assert value is None


def test_run_without_a_chip_exits_3_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_file_keeps_to_its_format():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    configs = {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    used = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        used.add(w["config"])
    assert used == configs
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
