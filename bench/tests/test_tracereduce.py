"""The trace reduction, on small recorded extracts."""
import json
import pathlib

import pytest

from bench.lib import tracereduce as tr

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture
def small():
    return json.loads((DATA / "trace_small.json").read_text())


def test_window_and_busy_union(small):
    win = tr.window(small, "bench.run_epoch")
    assert win == (1000, 11000)
    ops = tr.in_window(small["device"], win)
    assert len(ops) == 5                        # fusion.4 lies after it
    # device 0: [1000, 3500] + [5000, 7000] + [10500, 11000] overlaps once;
    # device 1: [1000, 6000]; averaged over the two devices
    assert tr.busy_ns(ops, win) == pytest.approx((5000 + 5000) / 2)


def test_scope_attribution_forward_backward_and_whole_names(small):
    ops = tr.in_window(small["device"], tr.window(small, "bench.run_epoch"))
    sc = small["scopes"]
    # device 0's fusion.1 and device 1's fusion.1, summed; not *_tablex
    assert tr.scope_ns(ops, sc, "memory_update") == 2000 + 5000
    assert tr.scope_ns(ops, sc, "embed") == 1000 + 2000   # transpose(jvp())
    assert tr.scope_ns(ops, sc, "apply") == 0
    assert tr.module_ns(ops, "jit__randint") == 1000


def test_leaves_drop_an_op_that_spans_others():
    ops = [{"device": 0, "name": "while.1", "start_ns": 0, "dur_ns": 100},
           {"device": 0, "name": "fusion.2", "start_ns": 10, "dur_ns": 40},
           {"device": 0, "name": "fusion.3", "start_ns": 60, "dur_ns": 30},
           {"device": 0, "name": "fusion.4", "start_ns": 100, "dur_ns": 5},
           {"device": 1, "name": "while.1", "start_ns": 0, "dur_ns": 100}]
    kept = tr.leaves(ops)
    assert sorted((o["device"], o["name"]) for o in kept) == [
        (0, "fusion.2"), (0, "fusion.3"), (0, "fusion.4"), (1, "while.1")]


def test_hlo_scopes_from_compiled_text():
    text = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        '  %fusion.37 = f32[4,2]{1,0} fusion(%p), kind=kLoop, calls=%c, '
        'metadata={op_name="jit(train_step)/jvp(embed)/dot_general" '
        'stack_frame_id=3}',
        '  ROOT %tuple = (f32[]) tuple(%a), metadata={op_name="x/apply/add"}',
        "  %param.1 = f32[] parameter(0)"])
    assert tr.hlo_scopes(text) == {
        "fusion.37": "jit(train_step)/jvp(embed)/dot_general",
        "tuple": "x/apply/add"}


def test_kernel_time_by_kernel_name(small):
    ops = tr.in_window(small["device"], tr.window(small, "bench.run_epoch"))
    assert tr.kernel_ns(ops, "embed_attn") == (2000, 1)
    assert tr.kernel_ns(ops, "memory_update_table") == (0, 0)


def test_breakdown_ops_and_idle_gaps(small):
    win = tr.window(small, "bench.run_epoch")
    ops = tr.in_window(small["device"], win)
    bd = tr.breakdown(small, ops, win)
    assert bd["device_ops"][0] == [
        "jit(train_step)/jvp(memory_update)/dot_general", pytest.approx(7e-6)]
    # device 0 idles over [7000, 10500] inside a train_step span's tail
    # and over [3500, 5000] between steps
    assert bd["idle_gaps"][0] == ["bench.train_step (1 gaps)",
                                  pytest.approx(3.5e-6)]
    assert bd["idle_gaps"][1] == ["bench.run_epoch (1 gaps)",
                                  pytest.approx(1.5e-6)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


@pytest.fixture
def tpu_step():
    """One training step of tgn-pres.train.wikipedia as a TPU v5e traced it
    (every device op between two calls of the step, trimmed from a chip
    trace; the host span stands for the window)."""
    return json.loads((DATA / "trace_tpu_step.json").read_text())


def test_recorded_tpu_step(tpu_step):
    win = tr.window(tpu_step, "bench.run_epoch")
    ops = tr.in_window(tpu_step["device"], win)
    assert len(ops) == 983      # two while ops dropped for their bodies
    # the two Pallas kernels, one call each, under their custom-call names
    assert tr.kernel_ns(ops, "embed_attn") == (906101.0, 1)
    assert tr.kernel_ns(ops, "memory_update_table") == (99546.0, 1)
    busy = tr.busy_ns(ops, win)
    longest = max(o["dur_ns"] for o in ops)
    assert longest <= busy <= sum(o["dur_ns"] for o in ops)
    # the step program, and the host's eager negative sampling around it
    step = tr.module_ns(ops, "jit_train_step")
    assert 0.9 * busy < step < busy
    assert tr.module_ns(ops, "jit__randint") > 0
    # most of the 22 ms between two step calls the device sits idle, and
    # the host is outside the step call then
    assert busy / (win[1] - win[0]) < 0.5
    bd = tr.breakdown(tpu_step, ops, win)
    assert bd["device_ops"][0] == ["jit_train_step/_embed_attn_pallas.1",
                                   pytest.approx(906101e-9)]
    assert bd["idle_gaps"][0][0].startswith("bench.run_epoch")
