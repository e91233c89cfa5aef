"""The pluggable multi-layer embedding stack (docs/DESIGN.md §Embedding
stack): registry dispatch, n_layers=1 bit-exactness with the historical
single-layer engine, a hand-written NumPy 2-hop reference, multi-head
folding, Pallas-kernel routing, and end-to-end training at depth 2."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import batching
from repro.graph.events import EventBatch
from repro.graph.negatives import sample_negatives
from repro.models import embeddings, mdgnn, modules
from repro.models.mdgnn import MDGNNConfig
from repro.optim import optimizers
from repro.train import loop


def _cfg(variant="tgn", **kw):
    kw.setdefault("n_heads", 1)
    return MDGNNConfig(variant=variant, n_nodes=12, d_edge=4, d_mem=16,
                       d_msg=16, d_time=8, d_embed=16, n_neighbors=4, **kw)


def _batch(src, dst, t, d_edge=4, seed=42):
    n = len(src)
    rng = np.random.default_rng(seed)
    return EventBatch(
        src=jnp.asarray(src, jnp.int32), dst=jnp.asarray(dst, jnp.int32),
        t=jnp.asarray(t, jnp.float32),
        feat=jnp.asarray(rng.normal(size=(n, d_edge)), jnp.float32),
        mask=jnp.ones(n, bool))


def _warm_state(cfg, params, batches):
    """Fold a few batches into memory + ring buffers (no training)."""
    state = mdgnn.init_state(cfg)
    for b in batches:
        mem2, _ = mdgnn.memory_update(params, cfg, state["memory"], b)
        state = dict(state, memory=mem2,
                     neighbors=batching.update_neighbors(state["neighbors"], b))
        if cfg.variant == "apan":
            nodes, times, msgs, mask = mdgnn.compute_messages(
                params, cfg, state["memory"], b)
            state = dict(state, mailbox=mdgnn.update_mailbox(
                cfg, state["mailbox"], nodes, msgs, times, mask))
    return state


BATCHES = [([0, 1, 0], [6, 7, 8], [1.0, 2.0, 3.0]),
           ([2, 6, 1], [8, 9, 7], [4.0, 4.5, 5.0]),
           ([0, 3], [7, 6], [6.0, 7.0])]
QUERY_NODES = [0, 5, 6, 7]
QUERY_T = [8.0, 8.0, 8.0, 8.0]


def test_registry_resolves_all_variants():
    for variant, name in embeddings.VARIANT_EMBEDDINGS.items():
        emb = embeddings.get_embedding(_cfg(variant))
        assert emb.name == name
    with pytest.raises(ValueError):
        embeddings.get_embedding(_cfg().__class__(
            variant="nope", n_nodes=4, d_edge=2))


def _legacy_tgn_embed(params, cfg, state, nodes, t_query):
    """The pre-registry single-layer / single-head embed_nodes math,
    verbatim (the bit-exactness target)."""
    mem = state["memory"]
    e = params["emb"]["l0"]
    s = mem.mem[nodes].astype(jnp.float32)
    nbrs = state["neighbors"]["nbr"][nodes]
    nbr_t = state["neighbors"]["t"][nodes]
    valid = nbrs >= 0
    s_nbr = mem.mem[jnp.maximum(nbrs, 0)].astype(jnp.float32)
    dt = t_query[:, None] - nbr_t
    t_enc = modules.time_encode(params["time"], dt)
    kv_in = jnp.concatenate([s_nbr, t_enc], axis=-1)
    q = s @ e["wq"]
    k = kv_in @ e["wk"]
    v = kv_in @ e["wv"]
    scores = jnp.einsum("me,mke->mk", q, k) / jnp.sqrt(q.shape[-1])
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.any(valid, -1, keepdims=True), probs, 0.0)
    agg = jnp.einsum("mk,mke->me", probs, v)
    return jax.nn.relu(jnp.concatenate([agg, s], -1) @ e["wo"])


def test_single_layer_bit_exact_with_legacy_path():
    cfg = _cfg("tgn", n_layers=1, n_heads=1)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(0), cfg)
    state = _warm_state(cfg, params, [_batch(*b) for b in BATCHES])
    nodes = jnp.asarray(QUERY_NODES)
    tq = jnp.asarray(QUERY_T)
    got = mdgnn.embed_nodes(params, cfg, state, nodes, tq)
    want = _legacy_tgn_embed(params, cfg, state, nodes, tq)
    assert bool(jnp.all(got == want)), float(jnp.abs(got - want).max())


# ---------------------------------------------------------------------------
# Hand-written NumPy 2-hop reference
# ---------------------------------------------------------------------------


def _np_attention_layer(params_l, time_w, time_b, h_self, h_nbr, t_self,
                        t_nbr, valid, n_heads):
    """One temporal attention layer in NumPy. h_self (M, Din);
    h_nbr (M, K, Din); t_nbr/valid (M, K)."""
    m, kk = valid.shape
    dt = t_self[:, None] - t_nbr
    t_enc = np.cos(dt[..., None] * time_w + time_b)          # (M, K, d_time)
    kv_in = np.concatenate([h_nbr, t_enc], axis=-1)
    q = h_self @ params_l["wq"]                               # (M, E)
    k = kv_in @ params_l["wk"]                                # (M, K, E)
    v = kv_in @ params_l["wv"]
    e = q.shape[-1]
    dh = e // n_heads
    agg = np.zeros((m, e), np.float64)
    for h in range(n_heads):
        qh = q[:, h * dh:(h + 1) * dh]
        kh = k[:, :, h * dh:(h + 1) * dh]
        vh = v[:, :, h * dh:(h + 1) * dh]
        scores = np.einsum("me,mke->mk", qh, kh) / np.sqrt(dh)
        scores = np.where(valid, scores, -1e30)
        smax = scores.max(-1, keepdims=True)
        p = np.exp(scores - smax)
        p = p / p.sum(-1, keepdims=True)
        p = np.where(valid.any(-1, keepdims=True), p, 0.0)
        agg[:, h * dh:(h + 1) * dh] = np.einsum("mk,mke->me", p, vh)
    out = np.concatenate([agg, h_self], axis=-1) @ params_l["wo"]
    return np.maximum(out, 0.0)


def _np_two_hop_reference(params, cfg, state, nodes, t_query):
    """Recursive 2-hop TGN embedding, written independently of the engine's
    frontier machinery: h2(v, t) attends over h1(u, t_uv) of v's neighbours,
    each h1(u, t_uv) attends over the memory rows of u's neighbours."""
    mem = np.asarray(state["memory"].mem, np.float64)
    nbr = np.asarray(state["neighbors"]["nbr"])
    nbr_t = np.asarray(state["neighbors"]["t"])
    tw = np.asarray(params["time"]["w"], np.float64)
    tb = np.asarray(params["time"]["b"], np.float64)
    l0 = {k: np.asarray(v, np.float64)
          for k, v in params["emb"]["l0"].items()}
    l1 = {k: np.asarray(v, np.float64)
          for k, v in params["emb"]["l1"].items()}

    def h1(node_ids, times):
        """Layer-1 embeddings for a flat list of (node, query-time)."""
        n1 = nbr[node_ids]                       # (M, K)
        t1 = nbr_t[node_ids]
        valid = n1 >= 0
        h_nbr = mem[np.maximum(n1, 0)]           # (M, K, D)
        return _np_attention_layer(l0, tw, tb, mem[node_ids], h_nbr,
                                   times, t1, valid, cfg.n_heads)

    n1 = nbr[nodes]                              # (M, K) 1-hop frontier
    t1 = nbr_t[nodes]
    valid1 = n1 >= 0
    m, kk = n1.shape
    # layer-1 reps of the query nodes themselves ...
    h1_self = h1(nodes, t_query)
    # ... and of their neighbours, each at its recruiting edge time
    h1_nbr = h1(np.maximum(n1, 0).reshape(-1),
                t1.reshape(-1)).reshape(m, kk, -1)
    return _np_attention_layer(l1, tw, tb, h1_self, h1_nbr, t_query, t1,
                               valid1, cfg.n_heads)


@pytest.mark.parametrize("n_heads", [1, 2])
def test_two_hop_matches_numpy_reference(n_heads):
    cfg = _cfg("tgn", n_layers=2, n_heads=n_heads)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(1), cfg)
    state = _warm_state(cfg, params, [_batch(*b) for b in BATCHES])
    nodes = np.asarray(QUERY_NODES)
    tq = np.asarray(QUERY_T, np.float32)
    got = mdgnn.embed_nodes(params, cfg, state, jnp.asarray(nodes),
                            jnp.asarray(tq))
    want = _np_two_hop_reference(params, cfg, state, nodes, tq)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=1e-4)


# ---------------------------------------------------------------------------
# Multi-head + kernel routing
# ---------------------------------------------------------------------------


def test_multihead_single_head_fold_is_identity():
    """n_heads=1 through the multi-head fold must equal the plain
    single-head attention (and transitively the legacy path)."""
    rng = np.random.default_rng(0)
    m, kk, e = 5, 4, 16
    q = jnp.asarray(rng.normal(size=(m, e)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(m, kk, e)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(m, kk, e)), jnp.float32)
    valid = jnp.asarray(rng.random((m, kk)) > 0.3)
    out1 = embeddings.neighbor_attention(q, k, v, valid, _cfg(n_heads=1))
    ref = embeddings._sdpa_single_head(q, k, v, valid)
    assert bool(jnp.all(out1 == ref))


def test_multihead_differs_and_is_finite():
    cfg1, cfg2 = _cfg("tgn", n_heads=1), _cfg("tgn", n_heads=2)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(2), cfg2)
    state = _warm_state(cfg2, params, [_batch(*b) for b in BATCHES])
    nodes, tq = jnp.asarray(QUERY_NODES), jnp.asarray(QUERY_T)
    h1 = mdgnn.embed_nodes(params, cfg1, state, nodes, tq)
    h2 = mdgnn.embed_nodes(params, cfg2, state, nodes, tq)
    assert bool(jnp.all(jnp.isfinite(h2)))
    assert float(jnp.abs(h1 - h2).max()) > 1e-6  # heads genuinely used


def test_heads_must_divide_embed_dim():
    with pytest.raises(ValueError, match="divisible"):
        mdgnn.init_params(jax.random.PRNGKey(0), _cfg("tgn", n_heads=3))


@pytest.mark.parametrize("variant", ["tgn", "apan"])
@pytest.mark.parametrize("n_layers,n_heads", [(1, 1), (2, 2)])
def test_kernel_path_matches_reference_path(variant, n_layers, n_heads):
    """use_kernels=True (Pallas, interpret on CPU) must agree with the pure
    jnp path through the whole embedding stack."""
    cfg = _cfg(variant, n_layers=n_layers, n_heads=n_heads)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(3), cfg)
    state = _warm_state(cfg, params, [_batch(*b) for b in BATCHES])
    nodes, tq = jnp.asarray(QUERY_NODES), jnp.asarray(QUERY_T)
    h_ref = mdgnn.embed_nodes(params, cfg, state, nodes, tq)
    h_ker = mdgnn.embed_nodes(params, dataclasses.replace(cfg, use_kernels=True),
                              state, nodes, tq)
    np.testing.assert_allclose(np.asarray(h_ker), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# K-hop frontier expansion
# ---------------------------------------------------------------------------


def test_expand_frontiers_static_shapes_and_times():
    cfg = _cfg("tgn")
    params, _ = mdgnn.init_params(jax.random.PRNGKey(4), cfg)
    state = _warm_state(cfg, params, [_batch(*b) for b in BATCHES])
    nodes, tq = jnp.asarray(QUERY_NODES), jnp.asarray(QUERY_T)
    m, kk = len(QUERY_NODES), cfg.n_neighbors
    hops = batching.expand_frontiers(state["neighbors"], nodes, tq, 2)
    assert [h["nodes"].shape[0] for h in hops] == [m, m * kk, m * kk * kk]
    assert hops[1]["valid"].shape == (m, kk)
    assert hops[2]["valid"].shape == (m * kk, kk)
    # hop-1 times are the ring-buffer edge times of the hop-0 gather
    nbr_t = state["neighbors"]["t"][nodes].reshape(-1)
    np.testing.assert_array_equal(np.asarray(hops[1]["t"]), np.asarray(nbr_t))
    # invalid slots are clamped to node 0
    raw = state["neighbors"]["nbr"][nodes].reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(hops[1]["nodes"]), np.asarray(jnp.maximum(raw, 0)))


# ---------------------------------------------------------------------------
# End-to-end: 2-layer stack trains through train/loop.py
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernels", [False, True])
def test_two_layer_trains_end_to_end(use_kernels):
    cfg = _cfg("tgn", n_layers=2, n_heads=2, use_pres=True,
               use_kernels=use_kernels)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(5), cfg)
    state = mdgnn.init_state(cfg)
    opt = optimizers.adamw(1e-3)
    step = loop.make_train_step(cfg, opt)
    opt_state = opt.init(params)
    batches = [_batch(*b) for b in BATCHES]
    for i in range(1, len(batches)):
        neg = sample_negatives(jax.random.PRNGKey(i), batches[i], 6, 12)
        params, opt_state, state, metrics = step(
            params, opt_state, state, batches[i - 1], batches[i], neg)
        assert np.isfinite(float(metrics["loss"]))
    # l1-layer params received gradient updates
    p0, _ = mdgnn.init_params(jax.random.PRNGKey(5), cfg)
    diff = max(float(jnp.abs(a - b).max()) for a, b in
               zip(jax.tree.leaves(p0["emb"]["l1"]),
                   jax.tree.leaves(params["emb"]["l1"])))
    assert diff > 0


def test_kernel_and_reference_losses_agree_at_depth_2():
    cfg = _cfg("tgn", n_layers=2, n_heads=2, use_pres=True)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(6), cfg)
    state = mdgnn.init_state(cfg)
    opt = optimizers.adamw(1e-3)
    prev, pos = _batch(*BATCHES[0]), _batch(*BATCHES[1])
    neg = sample_negatives(jax.random.PRNGKey(9), pos, 6, 12)
    losses = []
    for uk in (False, True):
        step = loop.make_train_step(dataclasses.replace(cfg, use_kernels=uk),
                                    opt)
        # the step donates opt/model state — run each config on copies
        _, _, _, m = step(params, opt.init(params),
                          jax.tree.map(jnp.copy, state), prev, pos, neg)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)


# ---------------------------------------------------------------------------
# jodie_proj — TGL's JODIETimeEmbedding
# ---------------------------------------------------------------------------


def test_jodie_projection_at_a_hand_computed_elapsed_time():
    """h = s . (1 + (D w + b)), D = (t - t_last) / (t + 1): node 3, last
    updated at 4 and queried at 9, has D = 0.5; node 5, never updated
    (t_last 0) and queried at 3, has D = 0.75."""
    cfg = _cfg("jodie")
    params, _ = mdgnn.init_params(jax.random.PRNGKey(7), cfg)
    state = mdgnn.init_state(cfg)
    rng = np.random.default_rng(7)
    table = rng.normal(size=(cfg.n_nodes, cfg.d_mem)).astype(np.float32)
    last = np.zeros(cfg.n_nodes, np.float32)
    last[3] = 4.0
    state = dict(state, memory=modules.MemoryState(
        mem=jnp.asarray(table), last_update=jnp.asarray(last)))
    h = mdgnn.embed_nodes(params, cfg, state, jnp.asarray([3, 5]),
                          jnp.asarray([9.0, 3.0]))
    w = np.asarray(params["emb"]["l0"]["w"])[0]
    b = np.asarray(params["emb"]["l0"]["b"])
    want = np.stack([table[3] * (1.0 + (0.5 * w + b)),
                     table[5] * (1.0 + (0.75 * w + b))])
    np.testing.assert_allclose(np.asarray(h), want, rtol=1e-6)


def test_jodie_projection_is_tgls_normal_linear():
    """One weight and one bias of width d_mem, both drawn N(0, 1) as TGL's
    NormalLinear(1, d) draws them."""
    cfg = dataclasses.replace(_cfg("jodie"), d_mem=4096, d_embed=4096)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(8), cfg)
    l0 = params["emb"]["l0"]
    assert set(params["emb"]) == {"l0"} and set(l0) == {"w", "b"}
    assert l0["w"].shape == (1, 4096) and l0["b"].shape == (4096,)
    for v in (l0["w"], l0["b"]):
        assert abs(float(jnp.std(v)) - 1.0) < 0.05
        assert abs(float(jnp.mean(v))) < 0.05


@pytest.mark.parametrize("kw,match", [(dict(n_layers=2), "one layer"),
                                      (dict(n_layers=3), "one layer"),
                                      (dict(d_embed=8), "d_embed")])
def test_jodie_settings_without_a_published_form_raise(kw, match):
    """Stacked projection layers and an embedding wider or narrower than
    the memory have no source: building them raises."""
    with pytest.raises(ValueError, match=match):
        mdgnn.init_params(jax.random.PRNGKey(0),
                          dataclasses.replace(_cfg("jodie"), **kw))
