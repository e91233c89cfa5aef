"""A configuration enters the benchmark as new files and new entries: in a
copy of the benchmark's tree, a stand-in configuration (`tgn-pres` with an
RNN memory cell, its module re-exporting `tgn-pres`'s) runs a training
cell on the CPU past the harness's look for a chip, its sound program
passes the comparison and a planted fault fails it, and no file that was
there before is touched."""
import hashlib
import json
import pathlib
import re
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import cell, compare, train

BENCH = pathlib.Path(__file__).resolve().parents[1]
SEED = 2**33 + 303

STAND_IN_MODULE = '''"""Stand-in configuration: tgn-pres with an RNN memory cell."""
from bench.lib import cell

_tgn = cell.config_module("tgn-pres")
emb_shapes, embed, embed_flops = _tgn.emb_shapes, _tgn.embed, _tgn.embed_flops
extra_state, maintain_extra = _tgn.extra_state, _tgn.maintain_extra
EXACT, NOT_TAKEN = _tgn.EXACT, _tgn.NOT_TAKEN
'''


def _digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*")) if p.is_file()}


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A copy of BENCHMARK.json and `bench/`, which the harness reads from;
    yields its root and the digests of the files it began with."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    before = _digests(tmp_path / "bench")
    monkeypatch.setattr(cell, "BENCH", tmp_path / "bench")
    monkeypatch.setattr(cell, "ROOT", tmp_path)
    yield tmp_path, before
    assert {k: v for k, v in _digests(tmp_path / "bench").items()
            if k in before} == before


def _add_cell(root: pathlib.Path, config: str, module: str | None) -> str:
    """Files and entries only: the configuration, its module, a tiny
    training mix, the cell's limits and its BENCHMARK.json entries."""
    bench = root / "bench"
    base = cell.load_json(bench / "configs" / "tgn-pres.json")
    conf = dict(base, name=config,
                model=dict(base["model"], memory_cell="rnn"))
    (bench / "configs" / f"{config}.json").write_text(json.dumps(conf))
    if module is not None:
        (bench / "configs" / f"{config}.py").write_text(module)
    shutil.copy(bench / "tests" / "data" / "train.tiny.json",
                bench / "workloads" / "train.tiny.json")
    name = f"{config}.train.tiny"
    shutil.copy(bench / "limits" / "tgn-pres.train.wikipedia.json",
                bench / "limits" / f"{name}.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name=config,
                                file=f"bench/configs/{config}.json"))
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": "train.tiny", "chips": 1,
                              "why": "a stand-in"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tgn-pres.train.wikipedia" in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return name


def _unchanged(cfg, step):
    """A step that returns the state it was given."""
    def frozen(params, opt_state, state, *batches):
        copies = jax.tree.map(jnp.copy, (opt_state, state))
        out = step(params, *copies, *batches)
        return (params, opt_state, state, out[3])
    return frozen


@pytest.mark.parametrize("fault", [None, _unchanged])
def test_configuration_enters_as_files(tree, fault):
    root, _ = tree
    name = _add_cell(root, "tgn-pres-rnn", STAND_IN_MODULE)
    spec = cell.workload(name)
    assert spec["config"]["model"]["memory_cell"] == "rnn"
    assert spec["module"].embed is cell.config_module("tgn-pres").embed
    assert train.program_config(spec["config"], spec["traffic"]
                                ).memory_cell == "rnn"
    out = train.run_cell(spec["config"], spec["traffic"], spec["limits"],
                         SEED, 0.0, False, jax.devices(), time.perf_counter(),
                         fault=fault)
    ok, checks = compare.judge(out["numbers"], spec["limits"]["limits"])
    assert out["compiles_in_window"] == 0
    if fault is None:
        assert ok, checks
        # on the CPU the kernels run as their oracles, which alone keeps
        # `correct` false: the chip's runs demand compiled kernels
        assert out["correct"] == (not out["not_compiled"])
    else:
        assert not ok, checks
        assert out["numbers"]["update_norm_gap"] == pytest.approx(1.0)
        assert not out["correct"]


def test_configuration_without_a_module_names_the_file(tree):
    root, _ = tree
    name = _add_cell(root, "no-module", None)
    want = root / "bench" / "configs" / "no-module.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(want))):
        cell.workload(name)


@pytest.mark.parametrize("key,value", [("att_dropout", 0.2),
                                       ("dropout", 0.2),
                                       ("use_kernels", False)])
def test_a_model_key_the_program_does_not_take_is_refused(key, value):
    """A key with no field in the program's config, one NOT_TAKEN holds at
    another value, or one the harness sets: the configuration cannot run
    as it states."""
    config = cell.load_json(BENCH / "configs" / "tgn-pres.json")
    config = dict(config, model=dict(config["model"], **{key: value}))
    traffic = cell.load_json(BENCH / "tests" / "data" / "train.tiny.json")
    with pytest.raises(ValueError, match=key):
        train.program_config(config, traffic)
