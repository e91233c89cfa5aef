"""MDGNN engine semantics: batch-parallel vs sequential-oracle memory
transitions (the temporal-discontinuity object itself), the three embedding
variants, and full train/eval steps."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import batching
from repro.graph.events import EventBatch
from repro.graph.negatives import NegativeDraw, sample_negatives
from repro.models import mdgnn
from repro.models.mdgnn import MDGNNConfig
from repro.optim import optimizers
from repro.train import loop


def _cfg(variant="tgn", **kw):
    return MDGNNConfig(variant=variant, n_nodes=12, d_edge=4, d_mem=16,
                       d_msg=16, d_time=8, d_embed=16, n_neighbors=4, **kw)


def _batch(src, dst, t, d_edge=4, mask=None):
    n = len(src)
    rng = np.random.default_rng(42)
    return EventBatch(
        src=jnp.asarray(src, jnp.int32), dst=jnp.asarray(dst, jnp.int32),
        t=jnp.asarray(t, jnp.float32),
        feat=jnp.asarray(rng.normal(size=(n, d_edge)), jnp.float32),
        mask=jnp.ones(n, bool) if mask is None else jnp.asarray(mask))


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params, _ = mdgnn.init_params(jax.random.PRNGKey(0), cfg)
    state = mdgnn.init_state(cfg)
    return cfg, params, state


# ---------------------------------------------------------------------------
# Temporal discontinuity: batch-parallel vs sequential oracle
# ---------------------------------------------------------------------------


def test_no_pending_events_matches_sequential_oracle(setup):
    """With vertex-disjoint events, batch processing IS sequential
    processing — the memory tables must agree exactly."""
    cfg, params, state = setup
    b = _batch([0, 1, 2], [6, 7, 8], [1.0, 2.0, 3.0])
    mem_par, _ = mdgnn.memory_update(params, cfg, state["memory"], b)
    mem_seq = mdgnn.sequential_memory_update(params, cfg, state["memory"], b)
    np.testing.assert_allclose(np.asarray(mem_par.mem),
                               np.asarray(mem_seq.mem), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mem_par.last_update),
                               np.asarray(mem_seq.last_update), atol=1e-6)


def test_pending_events_cause_discontinuity(setup):
    """Two events sharing vertex 0: the parallel update must differ from the
    sequential oracle on that vertex (Fig. 2(b)) but agree elsewhere."""
    cfg, params, state = setup
    b = _batch([0, 0], [6, 7], [1.0, 2.0])
    assert float(batching.pending_fraction(b)) > 0
    mem_par, _ = mdgnn.memory_update(params, cfg, state["memory"], b)
    mem_seq = mdgnn.sequential_memory_update(params, cfg, state["memory"], b)
    d0 = float(jnp.abs(mem_par.mem[0] - mem_seq.mem[0]).max())
    assert d0 > 1e-6, "pending vertex must show temporal discontinuity"
    # vertex 6 (only in the first event) sees identical history in both
    np.testing.assert_allclose(np.asarray(mem_par.mem[6]),
                               np.asarray(mem_seq.mem[6]), atol=1e-5)
    # untouched vertices identical
    np.testing.assert_allclose(np.asarray(mem_par.mem[3]),
                               np.asarray(mem_seq.mem[3]), atol=1e-7)


def test_last_occurrence_write_semantics(setup):
    """Batch processing writes the chronologically-LAST occurrence's update
    (one update per node per batch)."""
    cfg, params, state = setup
    b2 = _batch([0, 0], [6, 7], [1.0, 2.0])
    mem2, info = mdgnn.memory_update(params, cfg, state["memory"], b2)
    # compute what the second event alone would write for vertex 0
    b_last = _batch([0], [7], [2.0])
    b_last = EventBatch(src=b_last.src, dst=b_last.dst, t=b_last.t,
                        feat=b2.feat[1:2], mask=b_last.mask)
    mem_last, _ = mdgnn.memory_update(params, cfg, state["memory"], b_last)
    np.testing.assert_allclose(np.asarray(mem2.mem[0]),
                               np.asarray(mem_last.mem[0]), atol=1e-6)
    # selected flags: occurrences are [src0, src0, dst6, dst7]
    np.testing.assert_array_equal(np.asarray(info["selected"]),
                                  [False, True, True, True])


def test_memory_update_respects_mask(setup):
    cfg, params, state = setup
    b = _batch([0, 1], [6, 7], [1.0, 2.0], mask=[True, False])
    mem2, _ = mdgnn.memory_update(params, cfg, state["memory"], b)
    assert float(jnp.abs(mem2.mem[1]).max()) == 0.0   # masked event ignored
    assert float(jnp.abs(mem2.mem[0]).max()) > 0.0


def test_mean_aggregator_differs_from_last(setup):
    cfg, params, state = setup
    cfg_mean = _cfg(aggregator="mean")
    b = _batch([0, 0], [6, 7], [1.0, 2.0])
    mem_last, _ = mdgnn.memory_update(params, cfg, state["memory"], b)
    mem_mean, _ = mdgnn.memory_update(params, cfg_mean, state["memory"], b)
    assert float(jnp.abs(mem_last.mem[0] - mem_mean.mem[0]).max()) > 1e-7


# ---------------------------------------------------------------------------
# Embedding variants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["tgn", "jodie", "apan"])
def test_embed_nodes_shapes_and_finiteness(variant):
    cfg = _cfg(variant)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(1), cfg)
    state = mdgnn.init_state(cfg)
    b = _batch([0, 1, 0], [6, 7, 8], [1.0, 2.0, 3.0])
    mem2, _ = mdgnn.memory_update(params, cfg, state["memory"], b)
    state = dict(state, memory=mem2,
                 neighbors=batching.update_neighbors(state["neighbors"], b))
    h = mdgnn.embed_nodes(params, cfg, state, jnp.asarray([0, 5, 6]),
                          jnp.asarray([4.0, 4.0, 4.0]))
    assert h.shape == (3, cfg.d_embed)
    assert bool(jnp.all(jnp.isfinite(h)))


def test_jodie_time_projection_depends_on_dt():
    cfg = _cfg("jodie")
    params, _ = mdgnn.init_params(jax.random.PRNGKey(2), cfg)
    state = mdgnn.init_state(cfg)
    b = _batch([0], [6], [1.0])
    mem2, _ = mdgnn.memory_update(params, cfg, state["memory"], b)
    state = dict(state, memory=mem2)
    h1 = mdgnn.embed_nodes(params, cfg, state, jnp.asarray([0]),
                           jnp.asarray([2.0]))
    h2 = mdgnn.embed_nodes(params, cfg, state, jnp.asarray([0]),
                           jnp.asarray([50.0]))
    assert float(jnp.abs(h1 - h2).max()) > 1e-6


def test_apan_mailbox_update():
    cfg = _cfg("apan", mailbox_size=3)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(3), cfg)
    state = mdgnn.init_state(cfg)
    b = _batch([0, 0], [6, 7], [1.0, 2.0])
    nodes, times, msgs, mask = mdgnn.compute_messages(params, cfg,
                                                      state["memory"], b)
    mb = mdgnn.update_mailbox(cfg, state["mailbox"], nodes, msgs, times, mask)
    assert int(mb["ptr"][0]) == 2          # node 0 received 2 messages
    assert int(mb["ptr"][6]) == 1
    assert float(jnp.abs(mb["msg"][0, :2]).max()) > 0
    assert float(jnp.abs(mb["msg"][1]).max()) == 0.0   # untouched node


# ---------------------------------------------------------------------------
# Train / eval steps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant,use_pres", [("tgn", False), ("tgn", True),
                                              ("jodie", True), ("apan", True)])
def test_train_step_updates_params_and_state(variant, use_pres):
    cfg = _cfg(variant, use_pres=use_pres)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(4), cfg)
    state = mdgnn.init_state(cfg)
    opt = optimizers.adamw(1e-3)
    opt_state = opt.init(params)
    step = loop.make_train_step(cfg, opt)
    prev = _batch([0, 1], [6, 7], [1.0, 2.0])
    pos = _batch([0, 2], [7, 8], [3.0, 4.0])
    neg = sample_negatives(jax.random.PRNGKey(5), pos, 6, 12)
    p2, opt_state, state2, metrics = step(params, opt_state, state, prev,
                                          pos, neg)
    assert np.isfinite(float(metrics["loss"]))
    # params changed
    diff = max(float(jnp.abs(a - b).max())
               for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert diff > 0
    # memory advanced for touched nodes
    assert float(jnp.abs(state2["memory"].mem[0]).max()) > 0
    if use_pres:
        assert float(jnp.sum(state2["pres"].n)) > 0   # trackers advanced
    pen = float(metrics["coherence_penalty"])
    assert 0.0 - 1e5 <= pen <= 2.0 + 1e-5


def test_pres_changes_memory_trajectory():
    """PRES fuses prediction with measurement — after trackers warm up the
    memory trajectory must differ from the standard run."""
    cfg_std = _cfg("tgn", use_pres=False)
    cfg_pres = _cfg("tgn", use_pres=True)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(6), cfg_std)
    opt = optimizers.adamw(1e-3)
    batches = [_batch([0, 0], [6, 7], [float(i), float(i) + 0.5])
               for i in range(1, 5)]
    mems = {}
    for name, cfg in [("std", cfg_std), ("pres", cfg_pres)]:
        state = mdgnn.init_state(cfg)
        opt_state = opt.init(params)
        step = loop.make_train_step(cfg, opt)
        p = params
        for i in range(1, len(batches)):
            neg = sample_negatives(jax.random.PRNGKey(i), batches[i], 6, 12)
            p, opt_state, state, _ = step(p, opt_state, state,
                                          batches[i - 1], batches[i], neg)
        mems[name] = np.asarray(state["memory"].mem)
    assert np.abs(mems["std"] - mems["pres"]).max() > 1e-6


def test_eval_step_runs(setup):
    cfg, params, state = setup
    eval_step = loop.make_eval_step(cfg)
    prev = _batch([0, 1], [6, 7], [1.0, 2.0])
    pos = _batch([0, 2], [7, 8], [3.0, 4.0])
    neg = sample_negatives(jax.random.PRNGKey(7), pos, 6, 12)
    state2, lp, ln = eval_step(params, state, prev, pos, neg)
    assert lp.shape == (2,) and ln.shape == (2,)
    assert bool(jnp.all(jnp.isfinite(lp))) and bool(jnp.all(jnp.isfinite(ln)))


def test_kernel_routed_train_step_matches_jnp():
    """gru_fn routed through the Pallas kernel (interpret) must give the same
    loss as the pure-jnp cell."""
    from repro.kernels import ops as kops
    cfg = _cfg("tgn", use_pres=True)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(8), cfg)
    state = mdgnn.init_state(cfg)
    opt = optimizers.adamw(1e-3)
    prev = _batch([0, 1], [6, 7], [1.0, 2.0])
    pos = _batch([0, 2], [7, 8], [3.0, 4.0])
    neg = sample_negatives(jax.random.PRNGKey(9), pos, 6, 12)
    outs = []
    for gru_fn in (None, kops.gru_cell_params):
        step = loop.make_train_step(cfg, opt, gru_fn=gru_fn)
        # the step donates opt/model state — run each routing on copies
        _, _, _, m = step(params, opt.init(params),
                          jax.tree.map(jnp.copy, state), prev, pos, neg)
        outs.append(float(m["loss"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5)


# ---------------------------------------------------------------------------
# Negatives drawn inside the step
# ---------------------------------------------------------------------------


def _stream_cfg(stream, **kw):
    return MDGNNConfig(variant="tgn", n_nodes=stream.num_nodes,
                       d_edge=stream.feat_dim, d_mem=16, d_msg=16, d_time=8,
                       d_embed=16, n_neighbors=4, use_pres=True, **kw)


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("kw", [{}, dict(use_kernels=True, dedup_embed=True)])
def test_in_step_draw_matches_host_sampled_negatives(tiny_stream, tiny_spec,
                                                     kw):
    """`run_epoch` draws each step's negatives inside the compiled step; a
    hand-written loop that samples them on the host from iterated
    `jax.random.split` and passes them as an EventBatch must give the same
    params, node state (memory, ring, PRES trackers), logits and loss."""
    cfg = _stream_cfg(tiny_stream, **kw)
    batches = tiny_stream.temporal_batches(100)[:5]
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    key = jax.random.PRNGKey(11)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(0), cfg)
    opt = optimizers.adamw(1e-3)
    step = loop.make_train_step(cfg, opt)

    seen = []

    def recording(*args):
        out = step(*args)
        seen.append((np.asarray(out[3]["logit_p"]),
                     np.asarray(out[3]["logit_n"])))
        return out

    p_in, _, s_in, res = loop.run_epoch(
        params, opt.init(params), mdgnn.init_state(cfg), batches, cfg,
        recording, key, dst)

    p, o, s = params, opt.init(params), mdgnn.init_state(cfg)
    k, host, losses = key, [], []
    for i in range(1, len(batches)):
        k, sub = jax.random.split(k)
        neg = sample_negatives(sub, batches[i], *dst)
        p, o, s, m = step(p, o, s, batches[i - 1], batches[i], neg)
        assert "neg_key" not in m
        host.append((np.asarray(m["logit_p"]), np.asarray(m["logit_n"])))
        losses.append(float(m["loss"]))

    _assert_trees_equal(p_in, p)
    _assert_trees_equal(s_in, s)
    _assert_trees_equal(seen, host)
    assert res.loss == float(np.mean(losses))


def test_in_step_draw_lowers_from_shape_structs(tiny_stream, tiny_spec):
    """The step lowers from ShapeDtypeStructs of the draw form (how a
    harness reads its compiled HLO), and hands back the key of iterated
    `jax.random.split` with four outputs."""
    cfg = _stream_cfg(tiny_stream)
    b = tiny_stream.temporal_batches(100)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(0), cfg)
    opt = optimizers.adamw(1e-3)
    key = jax.random.PRNGKey(3)
    args = (params, opt.init(params), mdgnn.init_state(cfg), b[0], b[1],
            NegativeDraw.start(key, (tiny_spec.n_users,
                                     tiny_spec.n_users + tiny_spec.n_items)))
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          args)
    step = loop.make_train_step(cfg, opt)
    assert step.lower(*shapes).compile() is not None
    out = step(*args)
    assert len(out) == 4 and "loss" in out[3]
    np.testing.assert_array_equal(np.asarray(out[3]["neg_key"]),
                                  np.asarray(jax.random.split(key)[0]))


def test_evaluate_in_step_draw_matches_host_sampled(tiny_stream, tiny_spec):
    """`evaluate` draws its negatives inside the eval step and returns the
    same AP/AUC and state as a loop that samples them on the host."""
    from repro.utils import metrics as metrics_lib
    cfg = _stream_cfg(tiny_stream)
    batches = tiny_stream.temporal_batches(100)
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    key = jax.random.PRNGKey(5)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(0), cfg)
    eval_step = loop.make_eval_step(cfg)
    s_in, ap_in, auc_in = loop.evaluate(params, mdgnn.init_state(cfg),
                                        batches, cfg, eval_step, key, dst)

    s, k, pos_all, neg_all = mdgnn.init_state(cfg), key, [], []
    for i in range(1, len(batches)):
        k, sub = jax.random.split(k)
        neg = sample_negatives(sub, batches[i], *dst)
        s, lp, ln = eval_step(params, s, batches[i - 1], batches[i], neg)
        pos_all.append(np.asarray(lp))
        neg_all.append(np.asarray(ln))
    pos, neg = np.concatenate(pos_all), np.concatenate(neg_all)
    _assert_trees_equal(s_in, s)
    assert ap_in == metrics_lib.average_precision(pos, neg)
    assert auc_in == metrics_lib.roc_auc(pos, neg)
