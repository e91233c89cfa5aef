"""The comparison that decides `correct` for a training cell."""
import numpy as np
import pytest

from bench.lib import compare


def _tree(scale=1.0):
    return {"a": {"w": np.full((3, 4), 0.5 * scale)},
            "b": np.full((5,), 2.0 * scale),
            "c": np.zeros((2,))}


def test_zero_gradient_leaves_are_left_out():
    g = _tree()
    assert compare.counted_leaves(g) == ["a/w", "b"]


def test_norm_gap_is_a_gap_of_norms_not_a_norm_of_differences():
    ref = {"w": np.array([1.0, -1.0, 1.0, -1.0])}
    flipped = {"w": np.array([-1.0, 1.0, 1.0, -1.0])}   # signs flipped
    assert compare.leaf_gaps(flipped, ref, ["w"]) == {"w": 0.0}
    gaps = compare.leaf_gaps({"w": 1.1 * ref["w"]}, ref, ["w"])
    assert gaps["w"] == pytest.approx(0.1)


def test_small_leaves_are_measured_against_the_median_leaf():
    ref = {"big": np.ones(100), "mid": np.ones(25), "tiny": np.full(4, 1e-2)}
    prog = {"big": np.ones(100), "mid": np.ones(25),
            "tiny": np.full(4, 2e-2)}
    gaps = compare.leaf_gaps(prog, ref, sorted(ref))
    assert max(gaps, key=gaps.get) == "tiny"
    assert gaps["tiny"] == pytest.approx(0.02 / 5.0)   # median norm is 5


def test_training_numbers_and_judge():
    p0 = _tree()
    ref = {"losses": [0.7, 0.6, 0.5], "grads": _tree(), "params0": p0,
           "params_end": _tree(1.01), "state_end": {"mem": np.ones((3, 2))}}
    same, _ = compare.training_numbers(ref, ref)
    assert same == {"loss_gap": 0.0, "first_loss_gap": 0.0,
                    "grad_norm_gap": 0.0, "grad_norm_gap.median": 0.0,
                    "update_norm_gap": 0.0, "update_norm_gap.median": 0.0,
                    "state_gap": 0.0, "state.mem": 0.0, "mismatch.mem": 0.0,
                    "state_mismatch": 0.0}
    frozen = dict(ref, params_end=p0)           # a step that changed nothing
    numbers, where = compare.training_numbers(frozen, ref)
    assert numbers["update_norm_gap"] == pytest.approx(1.0)
    assert numbers["update_norm_gap.median"] > 0.5
    limits = {k: 0.1 for k in numbers}
    ok, checks = compare.judge(numbers, limits)
    assert not ok and checks["update_norm_gap"]["limit"] == 0.1
    assert compare.judge(same, limits)[0]
    nan = dict(ref, losses=[float("nan")] * 3)
    assert not compare.judge(compare.training_numbers(nan, ref)[0],
                             limits)[0]


def test_state_numbers_hold_exact_tables_entry_by_entry():
    """A float table is held by its relative gap; a table of copied times,
    ids or counts by the number of entries that differ at all, so one
    wrong neighbour id reads 1 however large the table's values are."""
    ref = {"mem": np.ones((4, 2)), "nbr": np.arange(40).reshape(4, 10),
           "nbr_t": np.full((4, 10), 5e5), "last_update": np.zeros(4)}
    prog = {"mem": np.full((4, 2), 1.01), "nbr": ref["nbr"].copy(),
            "nbr_t": ref["nbr_t"], "last_update": ref["last_update"]}
    prog["nbr"][2, 3] += 1
    numbers, where = compare.state_numbers(prog, ref)
    assert numbers["state_gap"] == pytest.approx(0.01)
    assert where["state_gap"] == "mem"
    assert numbers["state_mismatch"] == 1.0
    assert numbers["mismatch.nbr"] == 1.0 and numbers["mismatch.mem"] == 8.0
    assert numbers["state.nbr"] < 1e-1
    assert where["state_mismatch"] == "nbr 1, nbr_t 0, last_update 0"
