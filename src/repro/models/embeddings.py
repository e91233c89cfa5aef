"""Pluggable EMBEDDING modules (Eq. 1's `EMB`) — the registry behind
`mdgnn.embed_nodes`.

Each entry implements the paper's EMBEDDING step for one model family:

    tgn_attn     — L-layer / L-hop temporal graph attention over the
                   neighbour ring buffers (TGN); layer l attends over the
                   layer l-1 embeddings of its temporal neighbours, with
                   genuine multi-head attention and an optional Pallas
                   kernel inner loop (kernels/ops.py::neighbor_attn)
    jodie_proj   — JODIE's time projection as TGL's JODIETimeEmbedding
                   computes it: h = s . (1 + (D w + b)), D = (t - t_last) /
                   (t + 1), on the identity GNN (one layer, d_embed = d_mem)
    apan_mailbox — stacked attention over a per-node mailbox of
                   propagated messages

Architecture notes in docs/DESIGN.md §Embedding stack. An embedding is a
pair of pure functions:

    init(emb_builder, cfg)                      — adds params under "emb"
    apply(params, cfg, state, nodes, t_query)   — (M,) ids -> (M, d_embed)

Depth semantics (`cfg.n_layers`): for tgn_attn each extra layer is an extra
HOP — the k-hop frontier expansion in `core/batching.py::expand_frontiers`
keeps every level a static (M, K**l) gather so the whole stack jits. For
apan, which has no recursive neighbourhood, extra layers stack extra
mailbox-attention layers on the same inputs; both reduce bit-exactly to
the historical single-layer path at n_layers=1. JODIE's projection has one
published form and no depth: jodie_init refuses n_layers != 1.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import batching
from repro.models import modules
from repro.train import annotate


@dataclasses.dataclass(frozen=True)
class Embedding:
    """A registered EMBEDDING module (init + apply pair)."""
    name: str
    init: Callable[..., None]
    apply: Callable[..., jnp.ndarray]


EMBEDDINGS: dict[str, Embedding] = {}

# Model variant -> registry entry. Kept separate so future variants can
# share an embedding (e.g. a DyRep variant reusing tgn_attn).
VARIANT_EMBEDDINGS = {
    "tgn": "tgn_attn",
    "jodie": "jodie_proj",
    "apan": "apan_mailbox",
}


def register(name: str, init, apply) -> Embedding:
    emb = Embedding(name=name, init=init, apply=apply)
    EMBEDDINGS[name] = emb
    return emb


def get_embedding(cfg) -> Embedding:
    try:
        return EMBEDDINGS[VARIANT_EMBEDDINGS[cfg.variant]]
    except KeyError:
        raise ValueError(f"no embedding registered for variant "
                         f"{cfg.variant!r}") from None


def _layer_name(l: int) -> str:
    return f"l{l}"


def _check_heads(cfg):
    if cfg.n_layers < 1:
        raise ValueError(f"n_layers={cfg.n_layers} must be >= 1")
    if cfg.d_embed % cfg.n_heads != 0:
        raise ValueError(f"d_embed={cfg.d_embed} not divisible by "
                         f"n_heads={cfg.n_heads}")


# ---------------------------------------------------------------------------
# Shared multi-head masked attention (reference path + Pallas routing)
# ---------------------------------------------------------------------------


def _sdpa_single_head(q, k, v, valid):
    """Single-head masked attention — THE kernel-parity oracle
    (`kernels/ref.py::neighbor_attn_ref`), shared instead of duplicated so
    the reference path and the Pallas validation target cannot drift. At
    fp32 the oracle is bit-identical to the historical embed_nodes inner
    loop (its extra casts are identities), so n_layers=1 / n_heads=1 stays
    bit-exact with the pre-registry path.
    q: (M, E); k, v: (M, K, E); valid: (M, K) bool."""
    from repro.kernels import ref as kref
    return kref.neighbor_attn_ref(q, k, v, valid)


def neighbor_attention(q, k, v, valid, cfg):
    """Multi-head masked neighbour attention, optionally routed through the
    Pallas kernel (`kernels/ops.py::neighbor_attn`) when cfg.use_kernels.

    Heads are folded into the row dimension — (M, E) -> (M*H, E/H) — so the
    kernel and the reference path share one single-head inner loop and the
    per-row VMEM tiling of the kernel is unchanged. For H=1 the folds are
    identity reshapes, so the output is bit-exact with the historical
    single-head path.
    """
    m, e = q.shape
    kk = k.shape[1]
    h = cfg.n_heads
    if h > 1:
        dh = e // h
        q = q.reshape(m * h, dh)
        k = k.reshape(m, kk, h, dh).swapaxes(1, 2).reshape(m * h, kk, dh)
        v = v.reshape(m, kk, h, dh).swapaxes(1, 2).reshape(m * h, kk, dh)
        valid = jnp.repeat(valid, h, axis=0)
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        from repro.train import routing
        agg = routing.replicated(cfg, functools.partial(
            kops.neighbor_attn, mode=cfg.kernels_mode))(q, k, v, valid)
    else:
        agg = _sdpa_single_head(q, k, v, valid)
    if h > 1:
        agg = agg.reshape(m, e)
    return agg


# ---------------------------------------------------------------------------
# tgn_attn — L-hop temporal graph attention
# ---------------------------------------------------------------------------


def tgn_init(emb, cfg):
    """Per-layer attention params. Layer 0 consumes memory rows (d_mem);
    deeper layers consume layer l-1 embeddings (d_embed). Logical axes stay
    ("embed", "mlp") per layer so the distributed rule tables shard every
    layer identically (docs/DESIGN.md §Sharding)."""
    _check_heads(cfg)
    for l in range(cfg.n_layers):
        d_in = cfg.d_mem if l == 0 else cfg.d_embed
        lb = emb.sub(_layer_name(l))
        lb.add("wq", (d_in, cfg.d_embed), ("embed", "mlp"))
        lb.add("wk", (d_in + cfg.d_time, cfg.d_embed), ("embed", "mlp"))
        lb.add("wv", (d_in + cfg.d_time, cfg.d_embed), ("embed", "mlp"))
        lb.add("wo", (cfg.d_embed + d_in, cfg.d_embed), ("embed", "mlp"))


def _tgn_layer(params, layer_params, h_self, h_nbr, t_self, t_nbr, valid, cfg):
    """One temporal-attention layer: rows of h_self attend over their K
    neighbours' layer l-1 representations, keyed by [h_nbr, phi(dt)]."""
    m = h_self.shape[0]
    kk = valid.shape[1]
    dt = t_self[:, None] - t_nbr.reshape(m, kk)
    t_enc = modules.time_encode(params["time"], dt)        # (M, K, d_time)
    kv_in = jnp.concatenate([h_nbr.reshape(m, kk, -1), t_enc], axis=-1)
    q = h_self @ layer_params["wq"]                         # (M, E)
    k = kv_in @ layer_params["wk"]                          # (M, K, E)
    v = kv_in @ layer_params["wv"]
    agg = neighbor_attention(q, k, v, valid, cfg)
    return jax.nn.relu(
        jnp.concatenate([agg, h_self], axis=-1) @ layer_params["wo"])


def _tgn_layer_compact(params, layer_params, h_self, h_child, t_self,
                       child, cfg):
    """One temporal-attention layer on the DEDUPLICATED frontier: rows of
    h_self gather their K neighbours' layer l-1 rows from the child hop's
    unique table (`h_child`) through the compaction inverse indices. With
    cfg.use_kernels the whole chain — gather, time-encode, Q/K/V, masked
    softmax, weighted sum — runs as the fused `embed_attn` kernel."""
    rows = h_self.shape[0]
    kk = child["valid"].shape[1]
    dt = t_self[:, None] - child["t_edge"]
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        from repro.train import routing
        agg = routing.replicated(cfg, functools.partial(
            kops.embed_attn, n_heads=cfg.n_heads, mode=cfg.kernels_mode))(
            h_self, h_child, child["inverse"].reshape(rows, kk), dt,
            child["valid"], params["time"]["w"], params["time"]["b"],
            layer_params["wq"], layer_params["wk"], layer_params["wv"])
    else:
        h_nbr = annotate.events(
            h_child[child["inverse"]]).reshape(rows, kk, -1)
        t_enc = modules.time_encode(params["time"], dt)
        kv_in = jnp.concatenate([h_nbr, t_enc], axis=-1)
        q = h_self @ layer_params["wq"]
        k = kv_in @ layer_params["wk"]
        v = kv_in @ layer_params["wv"]
        agg = neighbor_attention(q, k, v, child["valid"], cfg)
    return jax.nn.relu(
        jnp.concatenate([agg, h_self], axis=-1) @ layer_params["wo"])


def _tgn_apply_dedup(params, cfg, state, nodes, t_query):
    """The unique-frontier path: hop d >= 1 holds one row per distinct
    (node, time) key (core/batching.py::expand_frontiers_unique), so every
    per-layer hidden state is computed once per unique entry and scattered
    back through the inverse indices. Hop 0 (the seeds) stays uncompacted
    — its rows ARE the outputs, and level-0 inputs are pure memory-row
    gathers, which keeps depth 1 bit-exact with the dense expansion."""
    mem = state["memory"]
    n_layers = cfg.n_layers
    hops = batching.expand_frontiers_unique(state["neighbors"], nodes,
                                            t_query, n_layers, cfg.n_nodes)
    h = [annotate.events(mem.mem[hop["nodes"]]).astype(jnp.float32)
         for hop in hops]
    for l in range(1, n_layers + 1):
        lp = params["emb"][_layer_name(l - 1)]
        h = [
            _tgn_layer_compact(params, lp, h[d], h[d + 1], hops[d]["t"],
                               hops[d + 1], cfg)
            for d in range(n_layers - l + 1)
        ]
    return h[0]


def tgn_apply(params, cfg, state, nodes, t_query):
    """L-hop temporal graph attention (TGN, Eq. 1's EMB).

    With cfg.dedup_embed (the default) each hop is compacted to its
    distinct (node, time) keys before any compute — per-layer work drops
    from sum_d M*K**d to sum_d min(rows_{d-1}, n_nodes)*K attention rows
    (docs/DESIGN.md §Embedding stack) — and cfg.use_kernels routes each
    layer through the gather-fused `embed_attn` Pallas kernel. The dense
    seed expansion below remains as the parity/bench baseline.
    """
    if cfg.dedup_embed:
        return _tgn_apply_dedup(params, cfg, state, nodes, t_query)
    return _tgn_apply_dense(params, cfg, state, nodes, t_query)


def _tgn_apply_dense(params, cfg, state, nodes, t_query):
    """The seed expansion (cfg.dedup_embed=False).

    Bottom-up over static frontiers: hop d holds (M*K**d,) node ids; layer l
    computes h^(l) for every frontier level still needed (0..L-l), attending
    over the h^(l-1) rows of the level-d+1 frontier. h^(0) is the memory
    table row. Total work is sum_d M*K**d per layer — the (M, K**l) shapes
    are all static, so the stack jits and shards like the 1-hop path.
    """
    mem = state["memory"]
    n_layers = cfg.n_layers
    hops = batching.expand_frontiers(state["neighbors"], nodes, t_query,
                                     n_layers)
    h = [annotate.events(mem.mem[hop["nodes"]]).astype(jnp.float32)
         for hop in hops]
    for l in range(1, n_layers + 1):
        lp = params["emb"][_layer_name(l - 1)]
        h = [
            _tgn_layer(params, lp, h[d], h[d + 1],
                       hops[d]["t"], hops[d + 1]["t"], hops[d + 1]["valid"],
                       cfg)
            for d in range(n_layers - l + 1)
        ]
    return h[0]


register("tgn_attn", tgn_init, tgn_apply)


# ---------------------------------------------------------------------------
# jodie_proj — time-projection embedding
# ---------------------------------------------------------------------------


def jodie_init(emb, cfg):
    """TGL's `JODIETimeEmbedding`: a `NormalLinear(1, d_mem)`, weight and
    bias both drawn N(0, 1/sqrt(1)). The weight is stored (1, d_mem), the
    (in, out) layout of every linear map here. The embedding is the
    projected memory row itself (TGL's identity GNN), so it has one layer
    and d_embed = d_mem; other settings have no source and raise."""
    if cfg.n_layers != 1:
        raise ValueError(f"jodie's embedding has one layer; n_layers="
                         f"{cfg.n_layers} has no published form")
    if cfg.d_embed != cfg.d_mem:
        raise ValueError(f"jodie's embedding is the projected memory row: "
                         f"d_embed={cfg.d_embed} must equal "
                         f"d_mem={cfg.d_mem}")
    l0 = emb.sub(_layer_name(0))
    l0.add("w", (1, cfg.d_mem), (None, "embed"), scale=1.0)
    l0.add("b", (cfg.d_mem,), ("embed",), scale=1.0)


def jodie_apply(params, cfg, state, nodes, t_query):
    """h = s . (1 + (D w + b)) with s the node's memory row and
    D = (t_query - t_last) / (t_query + 1) its elapsed time, normalised by
    the query time as TGL does, in float32."""
    mem = state["memory"]
    s = annotate.events(mem.mem[nodes]).astype(jnp.float32)
    l0 = params["emb"][_layer_name(0)]
    t_last = annotate.events(mem.last_update[nodes])
    dt = ((t_query - t_last) / (t_query + 1.0))[:, None]
    return s * (1.0 + (dt * l0["w"][0] + l0["b"]))


register("jodie_proj", jodie_init, jodie_apply)


# ---------------------------------------------------------------------------
# apan_mailbox — stacked attention over the propagated-message mailbox
# ---------------------------------------------------------------------------


def apan_init(emb, cfg):
    _check_heads(cfg)
    for l in range(cfg.n_layers):
        d_in = cfg.d_mem if l == 0 else cfg.d_embed
        lb = emb.sub(_layer_name(l))
        lb.add("wq", (d_in, cfg.d_embed), ("embed", "mlp"))
        lb.add("wk", (cfg.d_msg, cfg.d_embed), ("embed", "mlp"))
        lb.add("wv", (cfg.d_msg, cfg.d_embed), ("embed", "mlp"))
        lb.add("wo", (cfg.d_embed + d_in, cfg.d_embed), ("embed", "mlp"))


def apan_apply(params, cfg, state, nodes, t_query):
    mem = state["memory"]
    s = annotate.events(mem.mem[nodes]).astype(jnp.float32)
    msgs = annotate.events(state["mailbox"]["msg"][nodes])  # (M, Km, d_msg)
    valid = jnp.ones(msgs.shape[:2], bool)  # every mailbox slot attends
    h = s
    for l in range(cfg.n_layers):
        lp = params["emb"][_layer_name(l)]
        q = h @ lp["wq"]
        k = msgs @ lp["wk"]
        v = msgs @ lp["wv"]
        agg = neighbor_attention(q, k, v, valid, cfg)
        h = jax.nn.relu(jnp.concatenate([agg, h], axis=-1) @ lp["wo"])
    return h


register("apan_mailbox", apan_init, apan_apply)
