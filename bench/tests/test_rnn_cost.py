"""The cost of the fused table kernel's RNN form, kept beside the benchmark
in `bench/configs/jodie-pres.py`, checked by hand at small shapes, and the
reader of its roofline share on a hand-made trace."""
import json
import pathlib
import types

import numpy as np
import pytest

from bench.lib import cell, flops, tracereduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
GRU = {"variant": "tgn", "memory_cell": "gru", "d_mem": 4, "d_msg": 3,
       "d_time": 2, "d_embed": 4, "n_neighbors": 5}
RNN = dict(GRU, variant="jodie", memory_cell="rnn")


def _jodie():
    return cell.config_module("jodie-pres")


def test_rnn_form_counts_one_gate():
    f, b = _jodie().memory_update_table_rnn_cost(RNN, 6, 3)
    assert f == 2 * 6 * 3 * 4 + 2 * 6 * 4 * 4      # x W and h U, 1 gate
    expect = (6 * 16 + 3 * 16 + 6 * 12 + 6 * 16 + 6 * 16
              + (3 + 4 + 1) * 4 * 4 + 3 * 6 * 16)
    assert b == expect
    # one more written row costs one row of bytes, whatever the table size
    assert _jodie().memory_update_table_rnn_cost(RNN, 6, 4)[1] - b == 16


@pytest.mark.parametrize("occ,written", [(6, 3), (2000, 1312.5)])
def test_at_three_gates_it_is_the_gru_forms_cost(occ, written):
    assert _jodie().memory_update_table_rnn_cost(GRU, occ, written,
                                                 gates=3) == \
        flops.memory_update_table_cost(GRU, occ, written)


def test_embed_flops_count_the_projection():
    assert _jodie().embed_flops(RNN, 8) == 2 * 8 * 1 * 4


def test_projection_at_a_hand_computed_elapsed_time():
    """h = s * (1 + (D w + b)), D = (t - t_last) / (t + 1): a row last
    updated at 4 and queried at 9 has D = 0.5."""
    import jax.numpy as jnp
    s = np.array([[1.0, -2.0, 0.5, 3.0]], np.float32)
    w = np.array([[0.2, -1.0, 4.0, 0.0]], np.float32)
    b = np.array([0.1, 0.0, -1.0, 2.0], np.float32)
    h = _jodie().embed(RNN, {"emb": {"l0": {"w": w, "b": b}}}, jnp.asarray(s),
                       jnp.asarray([4.0], jnp.float32), {},
                       jnp.asarray([0]), jnp.asarray([9.0], jnp.float32),
                       jnp.float32)
    want = s * (1.0 + (0.5 * w[0] + b))
    np.testing.assert_allclose(np.asarray(h), want, rtol=1e-6)


def test_roofline_reader_reads_the_rnn_form_only():
    """The reader times the RNN form's calls (`memory_update_table_rnn`,
    numbered or not) and leaves the GRU form's alone."""
    read = cell.metric_reader("memory_update_table_rnn_roofline")
    model = json.loads((ROOT / "bench/configs/jodie-pres.json").read_text()
                       )["model"]
    traffic = json.loads((ROOT / "bench/workloads/train.reddit.json"
                          ).read_text())
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ops = [{"name": "memory_update_table_rnn.3", "dur_ns": 40_000.0},
           {"name": "memory_update_table_rnn", "dur_ns": 60_000.0},
           {"name": "memory_update_table.1", "dur_ns": 1e9}]
    ctx = types.SimpleNamespace(
        ops=ops, written_per_step=1500.0, model=model, traffic=traffic,
        peaks=peaks, arch=_jodie(), tr=tracereduce, flops=flops)
    f, b = _jodie().memory_update_table_rnn_cost(model, 2000, 1500.0)
    least = max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
    assert read(ctx) == pytest.approx(100.0 * least / 50e-6)
    # a configuration without the RNN form's cost gives nothing to read
    ctx.arch = cell.config_module("tgn-pres")
    assert read(ctx) is None
