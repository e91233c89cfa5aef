"""JODIE-PRES, the configuration `jodie-pres`: the program's lag-one step
(`loop.make_train_step`, driven by the harness as a chip run drives it)
against the plain reference with the configuration's module
(`bench/configs/jodie-pres.py`), on the CPU at a test's size: the losses,
the first gradient and the node state agree to float32 round-off, and the
elapsed time planted raw in the projection, where TGL normalises it, fails
the cell's comparison."""
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import cell, compare, reference, train

BENCH = pathlib.Path(__file__).resolve().parents[1]
TINY = cell.load_json(BENCH / "tests" / "data" / "train.tiny.json")
SEED = 2**33 + 707
WORKLOAD = "jodie-pres.train.reddit"


def _run():
    spec = cell.workload(WORKLOAD)
    return train.run_cell(spec["config"], TINY, spec["limits"], SEED, 0.0,
                          False, jax.devices(), time.perf_counter())


def _raw_elapsed_time(params, cfg, state, nodes, t_query):
    """JODIE's projection with the raw time since the last update, the
    form no source gives."""
    mem = state["memory"]
    s = mem.mem[nodes].astype(jnp.float32)
    l0 = params["emb"]["l0"]
    dt = (t_query - mem.last_update[nodes])[:, None]
    return s * (1.0 + (dt * l0["w"][0] + l0["b"]))


def test_lag_one_steps_match_the_reference():
    out = _run()
    n = out["numbers"]
    # the program's XLA:CPU float32 against the reference's float32 at
    # `highest`: the same operations, apart in their rounding only
    assert n["loss_gap"] < 1e-5, n
    assert n["grad_norm_gap"] < 1e-4, n
    assert n["state_gap"] < 1e-5, n
    assert n["state_mismatch"] == 0, out["where"]
    ok, checks = compare.judge(n, cell.workload(WORKLOAD)["limits"]["limits"])
    assert ok, checks
    # the memory stage ran as the fused table kernel's RNN form
    assert set(out["dispatch"]) == {"memory_update_table_rnn"}


def test_raw_elapsed_time_fails(monkeypatch):
    from repro.models import embeddings
    emb = embeddings.EMBEDDINGS["jodie_proj"]
    monkeypatch.setitem(embeddings.EMBEDDINGS, "jodie_proj",
                        embeddings.Embedding(emb.name, emb.init,
                                             _raw_elapsed_time))
    out = _run()
    ok, checks = compare.judge(out["numbers"],
                               cell.workload(WORKLOAD)["limits"]["limits"])
    assert not ok, checks
    assert checks["loss_gap"]["value"] > checks["loss_gap"]["limit"], checks
    assert not out["correct"]


def test_control_in_bfloat16_fails():
    """The reference computed in bfloat16, the precision below the
    configuration's float32, fails the cell's comparison."""
    spec = cell.workload(WORKLOAD)
    out = _run()
    g = TINY["graph"]
    steps, p_end, s_end = reference.run(
        spec["module"], train.model_spec(spec["config"], TINY),
        out["ref"]["params0"], train.make_stream(TINY, SEED),
        TINY["batch_size"], (g["n_users"], g["n_users"] + g["n_items"]),
        jax.random.fold_in(train.seed_key(SEED), 1), TINY["check_steps"],
        dtype=jnp.bfloat16)
    stand_in = {"losses": [r["loss"] for r in steps],
                "grads": steps[0]["grads"],
                "params0": out["ref"]["params0"], "params_end": p_end,
                "state_end": s_end}
    numbers, _ = compare.training_numbers(stand_in, out["ref"],
                                          spec["module"].EXACT)
    ok, checks = compare.judge(numbers, spec["limits"]["limits"])
    assert not ok, checks
