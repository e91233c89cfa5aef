"""End-to-end kernel-execution-layer parity (docs/KERNELS.md §Dispatch).

With cfg.use_kernels the training/eval/pipelined steps route the full
memory-maintenance path through the registered Pallas kernels (the fused
memory_update_table under PRES, in its GRU or RNN form; gru_cell for the
GRU without PRES; pres_predict for the pipeline staleness fill). In interpret mode those
kernels are the same computation as the pure-jnp path, so one training step
must match it within 1e-5 for the params, the memory table and the logits —
the acceptance contract for the kernel layer.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.graph.negatives import sample_negatives
from repro.models import mdgnn
from repro.models.mdgnn import MDGNNConfig
from repro.optim import optimizers
from repro.train import loop, pipeline


def _cfg(stream, use_kernels, **kw):
    base = dict(variant="tgn", n_nodes=stream.num_nodes,
                d_edge=stream.feat_dim, d_mem=32, d_msg=32, d_time=16,
                d_embed=32, n_neighbors=5, use_pres=True,
                use_kernels=use_kernels)
    base.update(kw)
    return MDGNNConfig(**base)


def _init(cfg, seed=0):
    params, _ = mdgnn.init_params(jax.random.PRNGKey(seed), cfg)
    state = mdgnn.init_state(cfg)
    opt = optimizers.adamw(1e-3)
    return params, opt, opt.init(params), state


def _train_steps(stream, tiny_spec, cfg, n_steps=2):
    """Run n_steps sequential train steps; returns (params, state, metrics)."""
    batches = stream.temporal_batches(100)
    params, opt, opt_state, state = _init(cfg)
    step = loop.make_train_step(cfg, opt)
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    m = None
    for i in range(1, n_steps + 1):
        neg = sample_negatives(jax.random.PRNGKey(i), batches[i], *dst)
        params, opt_state, state, m = step(params, opt_state, state,
                                           batches[i - 1], batches[i], neg)
    return params, state, m


def _assert_tree_close(a, b, atol=1e-5):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x, np.float32), np.asarray(y, np.float32), atol=atol), a, b)


@pytest.mark.parametrize("case", [
    dict(memory_cell="gru", use_pres=True, delta_mode="transition"),  # fused
    dict(memory_cell="gru", use_pres=True, delta_mode="innovation"),  # fused
    dict(memory_cell="gru", use_pres=True, pres_scale="time"),        # fused
    dict(memory_cell="gru", use_pres=False),          # gru_cell kernel only
    dict(memory_cell="rnn", use_pres=True),           # fused, RNN form
])
def test_train_step_kernel_parity(tiny_stream, tiny_spec, case):
    """The acceptance contract: one (here: two, to exercise warm trackers)
    training step with use_kernels=True matches the pure-jnp path within
    atol=1e-5 for params, memory table and logits."""
    p0, s0, m0 = _train_steps(tiny_stream, tiny_spec,
                              _cfg(tiny_stream, False, **case))
    p1, s1, m1 = _train_steps(tiny_stream, tiny_spec,
                              _cfg(tiny_stream, True, **case))
    _assert_tree_close(p0, p1)
    np.testing.assert_allclose(np.asarray(s0["memory"].mem),
                               np.asarray(s1["memory"].mem), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s0["memory"].last_update),
                               np.asarray(s1["memory"].last_update), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s0["pres"].xi),
                               np.asarray(s1["pres"].xi), atol=1e-4)
    np.testing.assert_allclose(np.asarray(m0["logit_p"]),
                               np.asarray(m1["logit_p"]), atol=1e-4)


@pytest.mark.parametrize("kernels_mode", ["interpret", "oracle"])
def test_train_step_parity_pinned_modes(tiny_stream, tiny_spec, kernels_mode):
    """The execution policy must be numerics-neutral: pinning
    cfg.kernels_mode to either Pallas-interpret or the jitted oracle
    (docs/KERNELS.md §Execution policy) matches the pure-jnp path at the
    same acceptance bounds as the default route. This is the end-to-end
    guard that the fused memory_update_table kernel (gather + GRU/PRES +
    scatter through the aliased table) and its oracle agree through real
    occurrence patterns, not just the synthetic unit shapes."""
    p0, s0, m0 = _train_steps(tiny_stream, tiny_spec,
                              _cfg(tiny_stream, False))
    p1, s1, m1 = _train_steps(tiny_stream, tiny_spec,
                              _cfg(tiny_stream, True,
                                   kernels_mode=kernels_mode))
    _assert_tree_close(p0, p1)
    np.testing.assert_allclose(np.asarray(s0["memory"].mem),
                               np.asarray(s1["memory"].mem), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s0["memory"].last_update),
                               np.asarray(s1["memory"].last_update), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m0["logit_p"]),
                               np.asarray(m1["logit_p"]), atol=1e-4)


def test_eval_step_kernel_parity(tiny_stream, tiny_spec):
    batches = tiny_stream.temporal_batches(100)
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    outs = []
    for use_kernels in (False, True):
        cfg = _cfg(tiny_stream, use_kernels)
        params, _, _, state = _init(cfg)
        step = loop.make_eval_step(cfg)
        neg = sample_negatives(jax.random.PRNGKey(7), batches[1], *dst)
        state2, lp, ln = step(params, state, batches[0], batches[1], neg)
        outs.append((state2["memory"].mem, lp, ln))
    np.testing.assert_allclose(np.asarray(outs[0][0]), np.asarray(outs[1][0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[0][1]), np.asarray(outs[1][1]),
                               atol=1e-4)


def test_pipelined_step_kernel_parity(tiny_stream, tiny_spec):
    """Depth-2 pipelined schedule: the kernel path (fused memory_update +
    pres_predict staleness fill) matches the jnp path step for step."""
    batches = tiny_stream.temporal_batches(100)
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    results = []
    for use_kernels in (False, True):
        cfg = _cfg(tiny_stream, use_kernels, pipeline_depth=2)
        params, opt, opt_state, state = _init(cfg)
        step = pipeline.make_train_step(cfg, opt)
        pstate = pipeline.PipelineState.init(state["memory"])
        m = None
        for i in range(1, 4):
            neg = sample_negatives(jax.random.PRNGKey(i), batches[i], *dst)
            params, opt_state, state, pstate, m = step(
                params, opt_state, state, pstate, batches[i - 1], batches[i],
                neg)
        results.append((params, state["memory"].mem, m["logit_p"]))
    _assert_tree_close(results[0][0], results[1][0])
    np.testing.assert_allclose(np.asarray(results[0][1]),
                               np.asarray(results[1][1]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(results[0][2]),
                               np.asarray(results[1][2]), atol=1e-4)


def test_stale_read_table_kernel_parity(tiny_stream):
    """The pres_predict kernel fill equals pres.predict over the whole
    table, with warm (non-zero) GMM trackers and non-trivial pending
    counts."""
    from repro.core import pres as pres_lib
    rng = np.random.default_rng(0)
    n, d = tiny_stream.num_nodes, 32
    pres_state = pres_lib.PresState(
        n=jnp.asarray(rng.integers(0, 5, size=(n, 2)), jnp.float32),
        xi=jnp.asarray(rng.normal(size=(n, 2, d)) * 0.1, jnp.float32),
        psi=jnp.abs(jnp.asarray(rng.normal(size=(n, 2, d)), jnp.float32)))
    mem = mdgnn.MemoryState(
        mem=jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
        last_update=jnp.abs(jnp.asarray(rng.normal(size=(n,)), jnp.float32)))
    pstate = pipeline.PipelineState(
        read_mem=mem.mem, read_last_update=mem.last_update,
        pending=jnp.asarray(rng.integers(0, 4, size=(n,)), jnp.float32),
        tick=jnp.zeros((), jnp.int32))
    live_t = mem.last_update + 1.0
    tables = []
    for use_kernels in (False, True):
        cfg = _cfg(tiny_stream, use_kernels, d_mem=d)
        tables.append(pipeline.stale_read_table(cfg, pres_state, pstate,
                                                live_t))
    assert float(jnp.abs(tables[0] - pstate.read_mem).max()) > 0  # fill acted
    np.testing.assert_allclose(np.asarray(tables[0]), np.asarray(tables[1]),
                               atol=1e-6)


def test_explicit_gru_fn_suppresses_fused_path(tiny_stream, tiny_spec):
    """make_train_step's contract: an explicitly passed gru_fn overrides the
    memory cell even when the fused memory_update kernel would otherwise
    engage (use_kernels + PRES + GRU)."""
    from repro.models import modules
    calls = []

    def spy_cell(params, x, h):
        calls.append(1)
        return modules.gru_cell(params, x, h)

    cfg = _cfg(tiny_stream, True)
    batches = tiny_stream.temporal_batches(100)
    params, opt, opt_state, state = _init(cfg)
    step = loop.make_train_step(cfg, opt, gru_fn=spy_cell)
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    neg = sample_negatives(jax.random.PRNGKey(0), batches[1], *dst)
    step(params, opt_state, state, batches[0], batches[1], neg)
    assert calls  # traced through the override, not the fused kernel


def test_kernel_memory_cell_resolver(tiny_stream):
    """modules.kernel_memory_cell: registry adapter iff use_kernels+GRU."""
    from repro.models import modules
    assert modules.kernel_memory_cell(_cfg(tiny_stream, False)) is None
    assert modules.kernel_memory_cell(
        _cfg(tiny_stream, True, memory_cell="rnn")) is None
    fn = modules.kernel_memory_cell(_cfg(tiny_stream, True))
    from repro.kernels import ops as kops
    assert fn is kops.gru_cell_params


def test_jodie_entry_point_runs_the_rnn_table_kernel(tmp_path):
    """`launch/train.py --model jodie --pres --use-kernels` builds JODIE's
    published RNN memory cell and runs its whole memory stage as the fused
    table kernel's RNN form: the dispatch log holds
    `memory_update_table_rnn` and neither the split path's kernels
    (`pres_filter`, `gru_cell`) nor the GRU form."""
    import json
    from repro.kernels import ops as kops
    from repro.launch import train as train_cli
    out = tmp_path / "run.json"
    kops.reset_dispatch_log()
    train_cli.main(["--dataset", "wiki-small", "--model", "jodie", "--pres",
                    "--use-kernels", "--epochs", "1", "--batch-size", "2000",
                    "--d-mem", "8", "--json-out", str(out)])
    log = kops.dispatch_log()
    assert "memory_update_table_rnn" in log, log
    assert not {"pres_filter", "gru_cell", "memory_update_table"} & set(log)
    assert json.loads(out.read_text())["config"]["memory_cell"] == "rnn"
