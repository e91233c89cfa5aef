"""Unified MDGNN engine (Eq. 1 / Alg. 1 / Alg. 2).

The engine implements the shared MESSAGE -> MEMORY -> EMBEDDING pipeline with
batch-parallel semantics (the paper's temporal-discontinuity regime), the
sequential oracle (events processed one at a time — the "true" dynamics), and
the PRES hooks. Model variants differ in their EMBEDDING module, which is
resolved through the pluggable registry in `repro.models.embeddings`
(docs/DESIGN.md §Embedding stack):

    TGN   — L-hop multi-head temporal graph attention over the neighbour
            ring buffers (cfg.n_layers hops, cfg.n_heads heads; the inner
            attention loop routes through the Pallas kernel
            `kernels/ops.py::neighbor_attn` when cfg.use_kernels)
    JODIE — RNN memory cell and TGL's time projection
            h = s . (1 + (D w + b)), D = (t - t_last) / (t + 1)
    APAN  — stacked attention over a per-node mailbox of propagated messages

`VARIANT_MEMORY_CELLS` gives each variant its published memory cell, which
the entry points (launch/train.py, launch/serve.py) build.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import batching, coherence, pres
from repro.core.pres import PresState
from repro.train import annotate
from repro.graph.events import EventBatch
from repro.models import embeddings as embeddings_lib
from repro.models import modules
from repro.models.modules import MemoryState
from repro.nn.module import ParamBuilder


@dataclasses.dataclass(frozen=True)
class MDGNNConfig:
    variant: str                 # tgn | jodie | apan
    n_nodes: int
    d_edge: int
    d_mem: int = 100
    d_msg: int = 100
    d_time: int = 32
    d_embed: int = 100
    n_neighbors: int = 10
    n_layers: int = 1            # EMBEDDING depth: hops for tgn, stacked
                                 # layers for apan, 1 for jodie
                                 # (docs/DESIGN.md)
    n_heads: int = 2
    mailbox_size: int = 10       # APAN
    memory_cell: str = "gru"     # gru | rnn (VARIANT_MEMORY_CELLS)
    aggregator: str = "last"     # last | mean  (per-node message reduction)
    # PRES
    use_pres: bool = False       # prediction-correction filter (Sec. 5.1)
    use_smoothing: bool | None = None  # Eq. 10 objective; None -> follow use_pres
    beta: float = 0.1            # coherence-smoothing weight (Eq. 10)
    delta_mode: str = "transition"   # transition (Alg. 2) | innovation (Eq. 9)
    # Eq. 7 extrapolation scale: "count" scales the GMM delta by the node's
    # pending-event count in the batch (the number of sequential memory
    # transitions flattened into one — our TPU-era adaptation, measurably
    # better); "time" is the paper-literal (t2 - t1) scaling.
    pres_scale: str = "count"
    pres_clip: float = 1.0       # |extrapolation| bound (memory is tanh-ish)
    anchor_fraction: float = 1.0
    # Sec. 5.3 anchor-set approximation, TPU-shaped: GMM trackers are kept
    # for pres_buckets hash buckets (node -> node % pres_buckets) instead of
    # per node. None -> exact per-node trackers. Cuts tracker state and its
    # distributed-combine wire bytes by N/buckets (docs/EXPERIMENTS.md §Perf).
    pres_buckets: int | None = None
    # bf16 memory table halves HBM + collective bytes for the table at
    # production scale; compute stays fp32 (docs/EXPERIMENTS.md §Perf iter. 6)
    mem_dtype: str = "float32"
    # Unique-frontier compaction for the tgn_attn embedding stack
    # (docs/DESIGN.md §Embedding stack): dedupe each hop's (M*K**d,)
    # frontier to one row per distinct (node, time) key before the
    # per-layer attention, under the static budget
    # min(rows_{d-1}, n_nodes)*K. A pure indirection change — bit-exact
    # with the dense expansion at depth 1, <= 1e-5 deeper (different
    # matmul batching) — that shrinks depth-2+ frontiers multiplicatively
    # whenever the node-id space is smaller than the seed set.
    dedup_embed: bool = True
    use_kernels: bool = False    # route GRU/filter through Pallas kernels
    # Kernel execution mode forwarded to kernels/ops.py dispatch:
    # "auto" resolves per backend/autotune-cache (tpu -> compiled Pallas,
    # cpu -> the jitted oracle); "compiled" | "interpret" | "oracle" pin it
    # (docs/KERNELS.md §Execution policy). Only meaningful with use_kernels.
    kernels_mode: str = "auto"
    # Staleness-aware pipelined schedule (docs/PIPELINE.md): the embedding
    # stage reads a memory snapshot at most `pipeline_depth` batch-writes
    # stale, with PRES Eq. 7 extrapolation filling the in-flight rows.
    # 0 = strictly sequential Alg. 1/2 (bit-exact with the historical loop).
    pipeline_depth: int = 0
    # Scan-compiled macro-batch training (docs/SCAN.md): T consecutive
    # lag-one steps run device-resident under one jax.lax.scan dispatch,
    # negatives sampled in-step, metrics stacked on device. 1 = the
    # sequential per-batch loop (bit-exact). Mutually exclusive with
    # pipeline_depth >= 1 for now (repro.train.scan raises).
    scan_chunk: int = 1
    # Data path (docs/DATA.md): path to an on-disk memory-mapped event
    # store (tools/convert_events.py). Pure data-plumbing knob — batches
    # are bit-identical to the in-RAM loaders, only peak host RSS changes
    # — so it never touches compiled computations. None = in-RAM stream.
    event_store: str | None = None
    # Memory-parallel training over a real 1-D device mesh
    # (docs/DISTRIBUTED.md): every node table is partitioned by
    # node_id % n_shards and each batch's touched rows are delivered to
    # their owner shard with a single all-to-all (repro.train.routing).
    # 1 = the single-device path, untouched.
    n_shards: int = 1
    # Static per-(sender, owner) routing-lane row budget. None derives the
    # overflow-free default (the sender's occurrence-slice length); smaller
    # budgets shrink the all-to-all wire bytes but may mask overflowing
    # rows — the count is surfaced in the step metrics (route_overflow),
    # never silently dropped.
    shard_budget: int | None = None
    # Telemetry (docs/OBSERVABILITY.md): pack the per-step obs vector
    # (loss, Eq. 10 coherence cosine, PRES prediction-error stats,
    # staleness, event counts) inside the jitted step and flush it once
    # per epoch. Device-side accumulation only — the step loop performs no
    # additional host syncs, so the knob is safe to leave on in perf runs
    # (the CI overhead gate pins >= 0.9x events/sec).
    obs_metrics: bool = False


# The memory cell each variant is published with: TGN's GRU (TGL
# config/TGN.yml), JODIE's RNN (Kumar et al. 2019; TGL config/JODIE.yml
# `memory_update: rnn`), APAN's GRU as this repository builds it.
VARIANT_MEMORY_CELLS = {"tgn": "gru", "jodie": "rnn", "apan": "gru"}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(key, cfg: MDGNNConfig):
    b = ParamBuilder(key, jnp.float32)
    modules.time_encode_init(b, "time", cfg.d_time)
    modules.message_init(b, "msg", cfg.d_mem, cfg.d_edge, cfg.d_time, cfg.d_msg)
    cell_init, _ = modules.MEMORY_CELLS[cfg.memory_cell]
    cell_init(b, "mem", cfg.d_msg, cfg.d_mem)
    # EMBEDDING params come from the pluggable registry: per-layer subtrees
    # emb/l<i>/... with ("embed", "mlp") logical axes (docs/DESIGN.md).
    embeddings_lib.get_embedding(cfg).init(b.sub("emb"), cfg)
    dec = b.sub("dec")
    dec.add("w1", (2 * cfg.d_embed, cfg.d_embed), ("embed", "mlp"))
    dec.add("b1", (cfg.d_embed,), ("mlp",), init="zeros")
    dec.add("w2", (cfg.d_embed, 1), ("mlp", None))
    dec.add("b2", (1,), (None,), init="zeros")
    node_cls = b.sub("node_cls")
    node_cls.add("w1", (cfg.d_embed, cfg.d_embed), ("embed", "mlp"))
    node_cls.add("b1", (cfg.d_embed,), ("mlp",), init="zeros")
    node_cls.add("w2", (cfg.d_embed, 1), ("mlp", None))
    node_cls.add("b2", (1,), (None,), init="zeros")
    pres.pres_param_init(b, "pres")
    return b.params, b.axes


# ---------------------------------------------------------------------------
# Runtime state
# ---------------------------------------------------------------------------


def init_state(cfg: MDGNNConfig):
    state = {
        "memory": MemoryState.init(cfg.n_nodes, cfg.d_mem,
                                   dtype=jnp.dtype(cfg.mem_dtype)),
        "neighbors": batching.init_neighbors(cfg.n_nodes, cfg.n_neighbors),
        "pres": PresState.init(cfg.pres_buckets or cfg.n_nodes, cfg.d_mem),
    }
    if cfg.variant == "apan":
        state["mailbox"] = {
            "msg": jnp.zeros((cfg.n_nodes, cfg.mailbox_size, cfg.d_msg), jnp.float32),
            "t": jnp.zeros((cfg.n_nodes, cfg.mailbox_size), jnp.float32),
            "ptr": jnp.zeros((cfg.n_nodes,), jnp.int32),
        }
    return state


STATE_AXES: dict[str, Any] = {
    "memory": modules.MEMORY_STATE_AXES,
    "neighbors": batching.NEIGHBOR_AXES,
    "pres": pres.PRES_STATE_AXES,
    "mailbox": {"msg": ("nodes", None, "embed"), "t": ("nodes", None),
                "ptr": ("nodes",)},
}


# ---------------------------------------------------------------------------
# MESSAGE + MEMORY (batch-parallel semantics)
# ---------------------------------------------------------------------------


def compute_messages(params, cfg: MDGNNConfig, mem: MemoryState, batch: EventBatch):
    """Messages for every endpoint occurrence ([srcs..., dsts...])."""
    nodes, times, other, feat, mask = batching.node_occurrences(batch)
    # pin gathered rows to the event axes (see repro.train.annotate)
    s_self = annotate.events(mem.mem[nodes]).astype(jnp.float32)
    s_other = annotate.events(mem.mem[other]).astype(jnp.float32)
    dt = times - annotate.events(mem.last_update[nodes])
    t_enc = modules.time_encode(params["time"], dt)
    msgs = modules.message(params["msg"], s_self, s_other, feat, t_enc)
    return nodes, times, msgs, mask


def occurrence_order(nodes, times, mask):
    """Sort permutation grouping the occurrences by node (masked ones
    last), each node's chronologically-last occurrence FINAL within its
    group. This is the hazard-free processing order the fused
    memory_update_table kernel requires: the table pass walks occurrences
    sequentially through an aliased buffer, so every gather of a node's
    row must land before that node's (selected) write — grouping by node
    with the selected occurrence last guarantees it (the selection below
    flags exactly the final element of each group)."""
    big = jnp.where(mask, times, -jnp.inf)
    return jnp.lexsort((big, jnp.where(mask, nodes,
                                       jnp.iinfo(jnp.int32).max)))


def _last_occurrence_flags(nodes, times, mask):
    """True for the chronologically-last valid occurrence of each node."""
    m = nodes.shape[0]
    order = occurrence_order(nodes, times, mask)
    n_sorted = nodes[order]
    m_sorted = mask[order]
    is_last_sorted = jnp.concatenate(
        [(n_sorted[1:] != n_sorted[:-1]) | ~m_sorted[1:], jnp.ones((1,), bool)])
    flags = jnp.zeros(m, bool).at[order].set(is_last_sorted & m_sorted)
    return flags


def scatter_rows(table, write_idx, values):
    """Masked row scatter with the drop-slot trick: index n_nodes (one past
    the end) is a dump row for masked-off updates, so the scatter itself
    stays dense and branch-free."""
    pad = jnp.zeros((1,) + table.shape[1:], table.dtype)
    out = jnp.concatenate([table, pad])
    return out.at[write_idx].set(values.astype(table.dtype), mode="drop")[:-1]


def memory_inputs(params, cfg: MDGNNConfig, mem: MemoryState,
                  batch: EventBatch):
    """MESSAGE stage + per-occurrence bookkeeping shared by the cell-based
    memory update below and the fused-kernel path
    (train/loop.py::_fused_memory_update).

    Returns (nodes, times, msgs, mask, selected, h_prev)."""
    nodes, times, msgs, mask = compute_messages(params, cfg, mem, batch)
    if cfg.aggregator == "mean":
        mean_n, _ = batching.mean_per_node(nodes, msgs, mask, cfg.n_nodes)
        msgs = mean_n[nodes]  # every occurrence carries its node's mean message
    selected = _last_occurrence_flags(nodes, times, mask)
    h_prev = mem.mem[nodes].astype(jnp.float32)  # (2b, D)
    return nodes, times, msgs, mask, selected, h_prev


def memory_update(params, cfg: MDGNNConfig, mem: MemoryState, batch: EventBatch,
                  gru_fn=None, defer_write: bool = False):
    """Batch-parallel memory transition: ONE update per touched node (the
    temporal-discontinuity semantics, Fig. 2(b) bottom). O(|B|) compute —
    the memory cell runs on the 2b endpoint occurrences, and only the
    selected (chronologically-last) occurrence per node is written back.

    Returns (new_mem_state, info) where info carries the occurrence rows
    needed by PRES and the coherence loss. With defer_write=True the mem
    table write is skipped (PRES overwrites the same rows with the fused
    values — writing twice costs a full extra scatter+combine at production
    scale, docs/EXPERIMENTS.md §Perf iteration 5)."""
    nodes, times, msgs, mask, selected, h_prev = memory_inputs(
        params, cfg, mem, batch)
    _, cell = modules.MEMORY_CELLS[cfg.memory_cell]
    if gru_fn is not None and cfg.memory_cell == "gru":
        cell = gru_fn
    new_rows = cell(params["mem"], msgs, h_prev)  # (2b, D)
    # compact-update boundary (repro.train.annotate): replicate the (2b, D)
    # update rows so the table scatter below is provably local under GSPMD
    new_rows = annotate.compact(new_rows)
    times = annotate.compact(times)
    selected = annotate.compact(selected)
    nodes = annotate.compact(nodes)
    write_idx = jnp.where(selected, nodes, cfg.n_nodes)
    if defer_write:
        new_mem = mem.mem
    else:
        new_mem = scatter_rows(mem.mem, write_idx, new_rows)
    new_t = scatter_rows(mem.last_update, write_idx, times)
    info = {
        "nodes": nodes, "selected": selected, "mask": mask,
        "s_prev": h_prev, "s_meas": new_rows,
        "t_prev": mem.last_update[nodes], "t_now": times,
        "msgs": msgs,
    }
    return MemoryState(mem=new_mem, last_update=new_t), info


def sequential_memory_update(params, cfg: MDGNNConfig, mem: MemoryState,
                             batch: EventBatch):
    """Sequential oracle: events processed strictly one at a time (the
    middle row of Fig. 2(b) — no temporal discontinuity)."""
    _, cell = modules.MEMORY_CELLS[cfg.memory_cell]

    def step(carry, ev):
        m, lu = carry
        src, dst, t, feat, mask = ev
        pair = jnp.stack([src, dst])
        other = jnp.stack([dst, src])
        s_self = m[pair].astype(jnp.float32)
        s_other = m[other].astype(jnp.float32)
        dt = t - lu[pair]
        t_enc = modules.time_encode(params["time"], dt)
        msgs = modules.message(params["msg"], s_self, s_other,
                               jnp.broadcast_to(feat, (2,) + feat.shape), t_enc)
        new_rows = cell(params["mem"], msgs, s_self)
        upd = jnp.where(mask, 1.0, 0.0)
        m = m.at[pair].set(
            (upd * new_rows + (1 - upd) * s_self).astype(m.dtype))
        lu = lu.at[pair].set(jnp.where(mask, t, lu[pair]))
        return (m, lu), None

    (m, lu), _ = jax.lax.scan(
        step, (mem.mem, mem.last_update),
        (batch.src, batch.dst, batch.t, batch.feat, batch.mask))
    return MemoryState(mem=m, last_update=lu)


# ---------------------------------------------------------------------------
# EMBEDDING modules
# ---------------------------------------------------------------------------


def embed_nodes(params, cfg: MDGNNConfig, state, nodes, t_query):
    """Dynamic embeddings h_i(t) for the given node ids at query times.

    Thin dispatch into the pluggable registry (repro.models.embeddings):
    the variant's embedding runs cfg.n_layers layers / hops with
    cfg.n_heads attention heads, routing the attention inner loop through
    the Pallas kernel when cfg.use_kernels (docs/DESIGN.md §Embedding
    stack)."""
    return embeddings_lib.get_embedding(cfg).apply(params, cfg, state,
                                                   nodes, t_query)


def update_mailbox(cfg: MDGNNConfig, mailbox, nodes, msgs, times, mask):
    """APAN: append each occurrence's message to the node's own mailbox ring
    (asynchronous propagation — endpoints receive each other's messages).
    Shares the ring scatter machinery with the neighbour buffers
    (`core/batching.py::ring_buffer_append`)."""
    bufs, ptr = batching.ring_buffer_append(
        {"msg": mailbox["msg"], "t": mailbox["t"]}, mailbox["ptr"],
        nodes, {"msg": msgs, "t": times}, mask)
    return {"msg": bufs["msg"], "t": bufs["t"], "ptr": ptr}


# ---------------------------------------------------------------------------
# Decoders
# ---------------------------------------------------------------------------


def link_logits(params, h_src, h_dst):
    x = jnp.concatenate([h_src, h_dst], axis=-1)
    h = jax.nn.relu(x @ params["dec"]["w1"] + params["dec"]["b1"])
    return (h @ params["dec"]["w2"] + params["dec"]["b2"])[..., 0]


def node_logits(params, h):
    hh = jax.nn.relu(h @ params["node_cls"]["w1"] + params["node_cls"]["b1"])
    return (hh @ params["node_cls"]["w2"] + params["node_cls"]["b2"])[..., 0]
