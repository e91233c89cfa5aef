"""The sequential loops pull each step's outputs `loop.PULL_LAG` steps after
its dispatch: the results equal a loop that pulls every step at once, an
epoch of a new length compiles nothing, and an aborted epoch still closes
its batch iterator."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.graph.negatives import NegativeDraw
from repro.models import mdgnn
from repro.models.mdgnn import MDGNNConfig
from repro.optim import optimizers
from repro.train import loop
from repro.utils import metrics as metrics_lib

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# epoch lengths, in steps, around the lag
LENGTHS = {"shorter": loop.PULL_LAG - 1, "equal": loop.PULL_LAG,
           "longer": loop.PULL_LAG + 3}


@pytest.fixture(scope="module")
def setup(tiny_stream, tiny_spec):
    cfg = MDGNNConfig(variant="tgn", n_nodes=tiny_stream.num_nodes,
                      d_edge=tiny_stream.feat_dim, d_mem=16, d_msg=16,
                      d_time=8, d_embed=16, n_neighbors=4, use_pres=True)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(0), cfg)
    opt = optimizers.adamw(1e-3)
    return dict(cfg=cfg, params=params, opt=opt,
                step=loop.make_train_step(cfg, opt),
                eval_step=loop.make_eval_step(cfg),
                batches=tiny_stream.temporal_batches(60),
                dst=(tiny_spec.n_users,
                     tiny_spec.n_users + tiny_spec.n_items))


def _eager_epoch(s, batches, key):
    """`run_epoch`'s steps with every output pulled right after dispatch."""
    p, o, st = s["params"], s["opt"].init(s["params"]), \
        mdgnn.init_state(s["cfg"])
    draw = NegativeDraw.start(key, s["dst"])
    losses, pos, neg = [], [], []
    for prev, batch in zip(batches, batches[1:]):
        draw = dataclasses.replace(draw, key=key)
        p, o, st, m = s["step"](p, o, st, prev, batch, draw)
        key = m.pop("neg_key")
        losses.append(float(m["loss"]))
        pos.append(np.asarray(m["logit_p"]))
        neg.append(np.asarray(m["logit_n"]))
    ap = metrics_lib.average_precision(np.concatenate(pos),
                                       np.concatenate(neg))
    aps = [metrics_lib.average_precision(a, b) for a, b in zip(pos, neg)]
    return (p, st), (ap, float(np.mean(losses)), aps)


def _eager_evaluate(s, batches, key):
    """`evaluate`'s steps with the logits pulled right after dispatch."""
    st = mdgnn.init_state(s["cfg"])
    draw = NegativeDraw.start(key, s["dst"])
    pos, neg = [], []
    for prev, batch in zip(batches, batches[1:]):
        draw = dataclasses.replace(draw, key=key)
        st, lp, ln, key = s["eval_step"](s["params"], st, prev, batch, draw)
        pos.append(np.asarray(lp))
        neg.append(np.asarray(ln))
    pos, neg = np.concatenate(pos), np.concatenate(neg)
    return (st,), (metrics_lib.average_precision(pos, neg),
                   metrics_lib.roc_auc(pos, neg))


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("runner", ["run_epoch", "evaluate"])
@pytest.mark.parametrize("length", list(LENGTHS))
def test_lagged_pull_equals_eager_pull(setup, runner, length):
    """Epochs shorter than the lag, as long as it and longer give the
    same results, bit for bit, as a loop that pulls each step at once:
    `run_epoch`'s AP, mean loss and per-step APs, `evaluate`'s AP and
    AUC, and the params and node state either leaves."""
    s = setup
    n_steps = LENGTHS[length]
    assert n_steps >= 1
    batches = s["batches"][:n_steps + 1]
    key = jax.random.PRNGKey(7)
    if runner == "run_epoch":
        p, _, st, res = loop.run_epoch(
            s["params"], s["opt"].init(s["params"]),
            mdgnn.init_state(s["cfg"]), batches, s["cfg"], s["step"], key,
            s["dst"], collect_logits=True)
        got_trees, got = (p, st), (res.ap, res.loss, res.aps)
        trees, want = _eager_epoch(s, batches, key)
        assert len(res.aps) == n_steps
    else:
        st, ap, auc = loop.evaluate(s["params"], mdgnn.init_state(s["cfg"]),
                                    batches, s["cfg"], s["eval_step"], key,
                                    s["dst"])
        got_trees, got = (st,), (ap, auc)
        trees, want = _eager_evaluate(s, batches, key)
    assert got == want
    _assert_trees_equal(got_trees, trees)


@pytest.mark.parametrize("n_pushed", [1, loop.PULL_LAG, loop.PULL_LAG + 3])
def test_lagged_pull_converts_the_step_lag_behind(n_pushed):
    """Each push converts exactly the output `PULL_LAG` pushes back, once
    there is one; the drain returns every step, oldest first, as numpy."""
    pull = loop._LaggedPull()
    for i in range(n_pushed):
        pull.push((jnp.float32(i), jnp.full((3,), i, jnp.float32)))
        assert [int(x) for x, _ in pull.pulled] == list(
            range(max(0, i + 1 - loop.PULL_LAG)))
    out = pull.drain()
    assert [int(x) for x, _ in out] == list(range(n_pushed))
    assert all(isinstance(v, np.ndarray) for step in out for v in step)
    assert not pull.queued


def test_epoch_of_new_length_compiles_nothing(setup):
    """After one warm epoch of 3 steps, epochs of other lengths run with
    no compile: the lagged pull adds no program of its own."""
    s = setup
    key = jax.random.PRNGKey(3)

    def epoch(n_steps):
        return loop.run_epoch(
            s["params"], s["opt"].init(s["params"]),
            mdgnn.init_state(s["cfg"]), s["batches"][:n_steps + 1],
            s["cfg"], s["step"], key, s["dst"])

    epoch(3)
    seen = []

    def listen(event, duration, **kw):
        if event == COMPILE_EVENT:
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for n_steps in (1, 5, len(s["batches"]) - 1):
            epoch(n_steps)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert seen == []


class _FailingBatches:
    """Yields `good` batches, then raises; records whether it was closed."""

    def __init__(self, batches, good: int):
        self.batches, self.good, self.closed = batches, good, False

    def __iter__(self):
        return self

    def __next__(self):
        if self.good == 0:
            raise RuntimeError("batch source failed")
        self.good -= 1
        return self.batches.pop(0)

    def close(self):
        self.closed = True


@pytest.mark.parametrize("runner", ["run_epoch", "evaluate"])
def test_failing_iterator_propagates_and_is_closed(setup, runner):
    """A batch source that raises mid-epoch, with steps still queued in the
    lagged pull, raises out of the loop and is closed."""
    s = setup
    src = _FailingBatches(list(s["batches"]), good=loop.PULL_LAG + 2)
    key = jax.random.PRNGKey(1)
    with pytest.raises(RuntimeError, match="batch source failed"):
        if runner == "run_epoch":
            loop.run_epoch(s["params"], s["opt"].init(s["params"]),
                           mdgnn.init_state(s["cfg"]), src, s["cfg"],
                           s["step"], key, s["dst"])
        else:
            loop.evaluate(s["params"], mdgnn.init_state(s["cfg"]), src,
                          s["cfg"], s["eval_step"], key, s["dst"])
    assert src.closed
