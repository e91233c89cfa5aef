"""A whole run of a training cell at a test's size, on the CPU, past the
harness's look for a chip: a sound run passes the cell's comparison, and
each fault the cell can have, planted under the harness, fails it. The
control, the reference computed in bfloat16, fails it too."""
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import cell, compare, reference, train

BENCH = pathlib.Path(__file__).resolve().parents[1]
TINY = cell.load_json(BENCH / "tests" / "data" / "train.tiny.json")
SEED = 2**33 + 101


def _config(name):
    return cell.load_json(BENCH / "configs" / f"{name}.json")


def _limits(workload):
    return cell.load_json(BENCH / "limits" / f"{workload}.json")


def _run(config, limits, fault=None):
    return train.run_cell(config, TINY, limits, SEED, 0.0, False,
                          jax.devices(), time.perf_counter(), fault=fault)


@pytest.mark.parametrize("config", ["tgn-pres"])
def test_sound_run_passes(config):
    limits = _limits("tgn-pres.train.wikipedia")
    out = _run(_config(config), limits)
    ok, checks = compare.judge(out["numbers"], limits["limits"])
    assert ok, checks
    assert out["compiles_in_window"] == 0


def _unchanged(cfg, step):
    """A step that returns the state it was given."""
    def frozen(params, opt_state, state, *batches):
        copies = jax.tree.map(jnp.copy, (opt_state, state))
        out = step(params, *copies, *batches)
        return (params, opt_state, state, out[3])
    return frozen


def test_step_returning_its_state_unchanged_fails():
    limits = _limits("tgn-pres.train.wikipedia")
    out = _run(_config("tgn-pres"), limits, fault=_unchanged)
    ok, checks = compare.judge(out["numbers"], limits["limits"])
    assert not ok
    assert out["numbers"]["update_norm_gap"] == pytest.approx(1.0)
    assert not out["correct"]


def _ring_slot_altered(cfg, step):
    """A step whose neighbour ring comes back with one node id changed."""
    def altered(params, opt_state, state, *batches):
        params, opt_state, state, m = step(params, opt_state, state, *batches)
        nb = dict(state["neighbors"], nbr=state["neighbors"]["nbr"].at[0, 0]
                  .add(1))
        return params, opt_state, dict(state, neighbors=nb), m
    return altered


def test_ring_slot_altered_fails():
    """The exact tables are compared entry by entry: one wrong node id in
    one ring slot fails the run."""
    limits = _limits("tgn-pres.train.wikipedia")
    out = _run(_config("tgn-pres"), limits, fault=_ring_slot_altered)
    ok, checks = compare.judge(out["numbers"], limits["limits"])
    assert not ok, checks
    assert out["numbers"]["mismatch.nbr"] >= 1
    assert not out["correct"]


def test_half_the_batch_left_out_fails(monkeypatch):
    from repro.train import loop

    def half_bce(logit_p, logit_n, pos_mask, neg_mask):
        keep = jnp.arange(pos_mask.shape[0]) < pos_mask.shape[0] // 2
        return loop_bce(logit_p, logit_n, pos_mask & keep, neg_mask & keep)

    loop_bce = loop.link_bce
    monkeypatch.setattr(loop, "link_bce", half_bce)
    limits = _limits("tgn-pres.train.wikipedia")
    out = _run(_config("tgn-pres"), limits)
    ok, checks = compare.judge(out["numbers"], limits["limits"])
    assert not ok, checks
    assert not out["correct"]


def test_control_in_bfloat16_fails():
    config = _config("tgn-pres")
    limits = _limits("tgn-pres.train.wikipedia")
    g = TINY["graph"]
    stream = train.make_stream(TINY, SEED)
    key = jax.random.fold_in(train.seed_key(SEED), 1)
    arch = cell.config_module("tgn-pres")
    params = reference.init_params(arch,
                                   jax.random.fold_in(train.seed_key(SEED), 0),
                                   config["model"], g["feat_dim"])
    spec = train.model_spec(config, TINY)
    dst = (g["n_users"], g["n_users"] + g["n_items"])
    runs = {}
    for name, dtype in (("ref", jnp.float32), ("control", jnp.bfloat16)):
        steps, p_end, s_end = reference.run(
            arch, spec, params, stream, TINY["batch_size"], dst, key,
            TINY["check_steps"], dtype=dtype)
        runs[name] = {"losses": [s["loss"] for s in steps],
                      "grads": steps[0]["grads"],
                      "params0": jax.device_get(params),
                      "params_end": p_end, "state_end": s_end}
    numbers, _ = compare.training_numbers(runs["control"], runs["ref"])
    ok, checks = compare.judge(numbers, limits["limits"])
    assert not ok, checks


def test_traced_run_reduces_its_own_trace(tmp_path):
    """The traced path end to end on the CPU: the trace is written, its
    window is the host span of the traced epoch, and the per-layer readers
    run (the CPU has no TPU op line, so only the host-clock metric reads)."""
    from bench.lib import layers
    spec = {"config": _config("tgn-pres"), "traffic": TINY,
            "per_layer": [{"name": "mfu.train", "unit": "%"},
                          {"name": "idle_share.train", "unit": "%"},
                          {"name": "embed_attn_roofline", "unit": "%"}]}
    out = train.run_cell(spec["config"], TINY,
                         _limits("tgn-pres.train.wikipedia"), SEED, 0.5,
                         True, jax.devices(), time.perf_counter(),
                         trace_dir=tmp_path / "trace")
    metrics, busy_s, window_s, bd = layers.read_all(
        spec, out, jax.devices(),
        chip={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    assert set(metrics) == {"mfu.train"}
    assert metrics["mfu.train"]["value"] > 0
    assert window_s > 0 and busy_s == 0
    assert out["traced"]["steps"] == 4 and out["attempted"] >= 4
