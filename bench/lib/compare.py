"""The numbers that decide `correct` for a training cell.

The program and the reference each take the same first steps from the same
weights, events and negatives. Compared, each as a relative gap:

* `loss_gap`: over those steps, the largest |loss - reference loss| /
  |reference loss|; `first_loss_gap` the first step's alone.
* `grad_norm_gap`: the first step's gradient as the optimizer got it
  (Adam's first moment after one step, divided by 1 - b1), by the worst
  leaf: |norm - reference norm| / max(reference norm, median leaf's norm).
* `update_norm_gap`: the same for each weight's change over the steps.
  `grad_norm_gap.median` and `update_norm_gap.median` give the median
  leaf's gap instead of the worst.
* `state_gap`: the node state after the steps that holds computed floats
  (memory, PRES trackers), by the worst table:
  max |a - reference| / max(1, max |reference|).
* `state_mismatch`: the entries of the tables that only copy or count
  (last-update times, neighbour rings and their pointers, the trackers'
  event counts, and the tables the configuration's module names in
  `EXACT`) that differ from the reference at all; they hold event times,
  node ids and whole counts, so a sound run reads exactly 0.
  `state.<table>` and `mismatch.<table>` give each table's reading.

Leaves whose reference gradient is under a thousandth of the median leaf's
(the node classifier, which link prediction never reaches) move by
round-off alone under Adam and are left out of both norm comparisons.
"""
from __future__ import annotations

import numpy as np

ZERO_GRAD = 1e-3


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_leaves(tree[k], f"{prefix}{k}/"))
        return out
    return {prefix[:-1]: np.asarray(tree, np.float64)}


def _norms(tree) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in _leaves(tree).items()}


def counted_leaves(ref_grads) -> list:
    g = _norms(ref_grads)
    med = float(np.median(list(g.values())))
    return sorted(k for k, v in g.items() if v >= ZERO_GRAD * med)


def leaf_gaps(prog, ref, leaves) -> dict:
    """Per leaf, the relative gap between two trees' norms:
    |norm - reference norm| / max(reference norm, median leaf's norm)."""
    p, r = _norms(prog), _norms(ref)
    med = float(np.median([r[k] for k in leaves]))
    return {k: abs(p[k] - r[k]) / max(r[k], med, 1e-30) for k in leaves}


# shared node-state tables whose entries are copied times, node ids or
# whole counts
EXACT_TABLES = ("last_update", "nbr", "nbr_t", "ptr", "pres_n")


def state_numbers(prog: dict, ref: dict, exact=()) -> tuple:
    """({number: value}, {number: worst table}) for the node state:
    `state_gap` over the float tables, `state_mismatch` over
    `EXACT_TABLES` and the module's `exact` tables, and each table's own
    reading."""
    exact_tables = EXACT_TABLES + tuple(exact)
    out, where = {}, {}
    for k, r in ref.items():
        r = np.asarray(r, np.float64)
        p = np.asarray(prog[k], np.float64)
        diff = np.abs(p - r)
        out[f"state.{k}"] = float(np.max(diff)) / max(
            1.0, float(np.max(np.abs(r))))
        out[f"mismatch.{k}"] = float(np.count_nonzero(~(diff == 0)))
    floats = [k for k in ref if k not in exact_tables]
    where["state_gap"] = max(floats, key=lambda k: out[f"state.{k}"])
    out["state_gap"] = out[f"state.{where['state_gap']}"]
    exact = [k for k in ref if k in exact_tables]
    out["state_mismatch"] = float(sum(out[f"mismatch.{k}"] for k in exact))
    where["state_mismatch"] = ", ".join(
        f"{k} {int(out[f'mismatch.{k}'])}" for k in exact)
    return out, where


def training_numbers(prog: dict, ref: dict, exact=()) -> tuple:
    """prog / ref: {"losses": [...], "grads": the first step's gradient
    tree, "params0", "params_end", "state_end"}; `exact`: the module's own
    tables compared entry for entry. Returns ({name: value},
    {name: worst part})."""
    leaves = counted_leaves(ref["grads"])
    g_prog = _leaves(prog["grads"])
    g_ref = _leaves(ref["grads"])
    d_prog = _delta(prog["params_end"], prog["params0"])
    d_ref = _delta(ref["params_end"], ref["params0"])
    loss_gaps = [abs(a - b) / max(abs(b), 1e-30)
                 for a, b in zip(prog["losses"], ref["losses"])]
    out, where = {}, {}
    out["loss_gap"] = max(loss_gaps)
    where["loss_gap"] = f"step {int(np.argmax(loss_gaps)) + 1}"
    out["first_loss_gap"] = loss_gaps[0]
    for name, p, r in (("grad_norm_gap", g_prog, g_ref),
                       ("update_norm_gap", d_prog, d_ref)):
        gaps = leaf_gaps(p, r, leaves)
        where[name] = max(gaps, key=gaps.get)
        out[name] = gaps[where[name]]
        out[name + ".median"] = float(np.median(list(gaps.values())))
    state, state_where = state_numbers(prog["state_end"], ref["state_end"],
                                       exact)
    out.update(state)
    where.update(state_where)
    if not all(np.isfinite(v) for v in out.values()):
        out = {k: (v if np.isfinite(v) else float("inf"))
               for k, v in out.items()}
    return out, where


def _delta(end, start) -> dict:
    a, b = _leaves(end), _leaves(start)
    return {k: a[k] - b[k] for k in a}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every number the cell's limits name at or under
    its limit; checks maps each such name to {"value", "limit"}."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
