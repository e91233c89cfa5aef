"""Temporal batching machinery: pending events / pending sets (Defs. 1-2),
per-node last-message reduction (the batch-parallel semantics of Fig. 2(b)),
and neighbour ring buffers.

The per-node "one update per batch" reduction is exactly the paper's
temporal-discontinuity object: all but the chronologically-last message per
node within a batch are flattened away.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.events import EventBatch


# ---------------------------------------------------------------------------
# Pending sets (Defs. 1-2) — analysis utilities
# ---------------------------------------------------------------------------


def pending_counts(src, dst, t, mask=None) -> jnp.ndarray:
    """|P(e, B)| for every event e in the batch: the number of earlier events
    in the batch sharing a vertex. O(b^2) — an analysis probe, not a
    training-path op."""
    share = ((src[:, None] == src[None, :]) | (src[:, None] == dst[None, :]) |
             (dst[:, None] == src[None, :]) | (dst[:, None] == dst[None, :]))
    earlier = t[None, :] < t[:, None]
    pend = share & earlier
    if mask is not None:
        pend = pend & mask[None, :] & mask[:, None]
    return jnp.sum(pend, axis=1)


def pending_fraction(batch: EventBatch) -> float:
    """Fraction of events with a non-empty pending set — grows with batch
    size; the empirical knob behind Theorem 2."""
    cnt = pending_counts(batch.src, batch.dst, batch.t, batch.mask)
    valid = jnp.sum(batch.mask)
    return float(jnp.sum((cnt > 0) & batch.mask) / jnp.maximum(valid, 1))


# ---------------------------------------------------------------------------
# Per-node message reduction (batch-parallel memory update semantics)
# ---------------------------------------------------------------------------


def node_occurrences(batch: EventBatch):
    """Flatten a batch into per-endpoint occurrences.

    Returns (nodes (2b,), times (2b,), other (2b,), feat (2b,F), occ_mask)
    where entry order is [all srcs, all dsts]."""
    nodes = jnp.concatenate([batch.src, batch.dst])
    other = jnp.concatenate([batch.dst, batch.src])
    times = jnp.concatenate([batch.t, batch.t])
    feat = jnp.concatenate([batch.feat, batch.feat], axis=0)
    mask = jnp.concatenate([batch.mask, batch.mask])
    return nodes, times, other, feat, mask


def last_per_node(nodes, times, values, mask, num_nodes: int):
    """Chronologically-LAST value per node (TGN aggregator): returns
    (per_node_value (N,D), per_node_time (N,), touched (N,))."""
    big = jnp.where(mask, times, -jnp.inf)
    # sort by (node, time) and take the last entry of each node run
    order = jnp.lexsort((big, nodes))
    n_sorted = nodes[order]
    is_last = jnp.concatenate([n_sorted[1:] != n_sorted[:-1],
                               jnp.ones((1,), bool)])
    take = is_last & mask[order]
    idx = jnp.where(take, n_sorted, num_nodes)  # dump slot
    out = jnp.zeros((num_nodes + 1, values.shape[-1]), values.dtype)
    out = out.at[idx].set(values[order], mode="drop")
    t_out = jnp.zeros((num_nodes + 1,), times.dtype)
    t_out = t_out.at[idx].set(times[order], mode="drop")
    touched = jnp.zeros((num_nodes + 1,), bool).at[idx].set(True, mode="drop")
    return out[:num_nodes], t_out[:num_nodes], touched[:num_nodes]


def mean_per_node(nodes, values, mask, num_nodes: int):
    """Mean of messages per node (alternative aggregator)."""
    idx = jnp.where(mask, nodes, num_nodes)
    summed = jax.ops.segment_sum(values * mask[:, None], idx, num_segments=num_nodes + 1)
    cnt = jax.ops.segment_sum(mask.astype(values.dtype), idx, num_segments=num_nodes + 1)
    mean = summed / jnp.maximum(cnt[:, None], 1.0)
    return mean[:num_nodes], (cnt[:num_nodes] > 0)


# ---------------------------------------------------------------------------
# Temporal neighbour ring buffers (for the EMBEDDING module)
# ---------------------------------------------------------------------------


def init_neighbors(n_nodes: int, k: int):
    return {
        "nbr": jnp.full((n_nodes, k), -1, jnp.int32),
        "t": jnp.zeros((n_nodes, k), jnp.float32),
        "ptr": jnp.zeros((n_nodes,), jnp.int32),
    }


NEIGHBOR_AXES = {"nbr": ("nodes", None), "t": ("nodes", None), "ptr": ("nodes",)}


def ring_buffer_append(buffers, ptr, nodes, values, mask):
    """Scatter per-occurrence rows into per-node ring buffers.

    The shared scatter machinery behind the neighbour ring buffers and the
    APAN mailbox (docs/DESIGN.md §Embedding stack): multiple same-node
    occurrences within a batch land in consecutive slots (per-node rank via a
    stable sort), preserving within-batch order; masked rows are dropped via
    an out-of-range dump slot.

    buffers: dict name -> (N, K, ...) ring arrays sharing one write pointer
    ptr:     (N,) int32 next-slot pointer
    nodes:   (M,) int32 target node per row
    values:  dict name -> (M, ...) rows to append (keys must match buffers)
    mask:    (M,) bool row validity
    Returns (new_buffers, new_ptr).
    """
    probe = next(iter(buffers.values()))
    n, k = probe.shape[0], probe.shape[1]
    m = nodes.shape[0]
    # rank of each occurrence within its node (in array order = time order);
    # the searchsorted probe must use the MASKED keys — masked rows sort to
    # the end by key n but their raw node ids would leave the probe array
    # unsorted, corrupting the ranks of valid rows whenever padding is
    # present (pad-to-bucket serving made this visible: the fold must be
    # pad-invariant, tests/test_serve.py::test_ingest_pad_invariant)
    keys = jnp.where(mask, nodes, n)
    order = jnp.argsort(keys, stable=True)
    sorted_keys = keys[order]
    start = jnp.searchsorted(sorted_keys, jnp.arange(n + 1))
    rank_sorted = jnp.arange(m) - start[sorted_keys]
    rank = jnp.zeros(m, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    slot = (ptr[nodes] + rank) % k
    flat = jnp.where(mask, nodes * k + slot, n * k)
    out = {}
    for name, buf in buffers.items():
        tail = buf.shape[2:]
        fb = buf.reshape((n * k,) + tail)
        fb = jnp.concatenate([fb, jnp.zeros((1,) + tail, fb.dtype)])
        out[name] = (fb.at[flat].set(values[name].astype(fb.dtype),
                                     mode="drop")[:-1]
                     .reshape((n, k) + tail))
    counts = jax.ops.segment_sum(mask.astype(jnp.int32),
                                 jnp.where(mask, nodes, n),
                                 num_segments=n + 1)[:n]
    return out, (ptr + counts) % k


def update_neighbors(state, batch: EventBatch):
    """Append each event's endpoints to each other's ring buffers."""
    from repro.train import annotate
    nodes, times, other, _, mask = node_occurrences(batch)
    nodes, times = annotate.compact(nodes), annotate.compact(times)
    other, mask = annotate.compact(other), annotate.compact(mask)
    bufs, ptr = ring_buffer_append(
        {"nbr": state["nbr"], "t": state["t"]}, state["ptr"],
        nodes, {"nbr": other, "t": times}, mask)
    return {"nbr": bufs["nbr"], "t": bufs["t"], "ptr": ptr}


# ---------------------------------------------------------------------------
# K-hop frontier expansion (multi-layer EMBEDDING support)
# ---------------------------------------------------------------------------


def gather_frontier(neighbors, nodes):
    """One-hop temporal neighbourhood of `nodes` from the ring buffers.

    Returns (nbr (M, K) int32 with -1 for empty slots, t (M, K) fp32 edge
    times, valid (M, K) bool). Gathered rows are pinned to the event axes so
    the distributed spec shards the hop gathers (docs/DESIGN.md §Sharding).
    """
    from repro.train import annotate
    nbr = annotate.events(neighbors["nbr"][nodes])
    t = annotate.events(neighbors["t"][nodes])
    return nbr, t, nbr >= 0


def compact_unique(nodes, t, budget: int):
    """Static-shape segment-unique over (node, time) keys.

    The jittable dedup primitive behind the compacted frontier expansion
    (docs/DESIGN.md §Embedding stack): sort the N keys, flag run starts,
    and scatter each run's key into a compact `(budget,)` table — the same
    lexsort/boundary-flag machinery family as `last_per_node` /
    `mdgnn.occurrence_order`. `budget` must be a static upper bound on the
    number of distinct keys (callers derive a provably-sufficient one;
    overflow would silently drop rows via mode="drop", so never pass a
    heuristic bound). Returns a dict:

        nodes    (budget,) unique node ids (slots >= n_unique hold 0)
        t        (budget,) matching entry times
        inverse  (N,) int32 with uniq[inverse] == original, EXACTLY —
                 including clamped node-0 slots, which are genuine (0, t)
                 keys here and stay masked by `valid` downstream
        n_unique ()  int32 measured distinct-key count (<= budget)
    """
    n = nodes.shape[0]
    budget = int(min(budget, n))
    # lexsort((t, nodes)) as two stable one-key sorts: the same permutation,
    # which the TPU compiler builds ~3x faster at frontier sizes (~30k keys)
    order = jnp.argsort(t, stable=True)
    order = order[jnp.argsort(nodes[order], stable=True)]
    ns, ts = nodes[order], t[order]
    new = jnp.concatenate([jnp.ones((1,), bool),
                           (ns[1:] != ns[:-1]) | (ts[1:] != ts[:-1])])
    slot = (jnp.cumsum(new) - 1).astype(jnp.int32)
    uniq_nodes = jnp.zeros((budget,), nodes.dtype).at[slot].set(ns,
                                                                mode="drop")
    uniq_t = jnp.zeros((budget,), t.dtype).at[slot].set(ts, mode="drop")
    inverse = jnp.zeros((n,), jnp.int32).at[order].set(slot)
    return {"nodes": uniq_nodes, "t": uniq_t, "inverse": inverse,
            "n_unique": slot[-1] + 1}


def expand_frontiers_unique(neighbors, nodes, t_query, n_hops: int,
                            n_nodes: int):
    """Deduplicated k-hop expansion: each hop holds one row per DISTINCT
    (node, entry-time) pair instead of the raw (M * K**d,) multiset.

    A frontier entry's embedding depends only on its (node, time) key (plus
    shared state/params), so duplicates are pure re-computation. Hop 0 is
    the seed set, uncompacted — its rows ARE the caller's outputs. Hop
    d >= 1 compacts the expansion of hop d-1's unique rows under the static
    budget

        U_d = min(U_{d-1}, n_nodes) * K

    which is provably sufficient: the expansion's keys are ring-buffer
    slots of hop d-1's distinct node ids (<= min(U_{d-1}, n_nodes) of
    them), each contributing at most K distinct (neighbour, edge-time)
    pairs. On streams whose node-id space is smaller than the seed set
    (power-law graphs at production batch sizes) the budget shrinks deep
    frontiers multiplicatively vs the raw K**d growth.

    hop 0: {"nodes": (M,), "t": (M,)}
    hop d: compact_unique output over the raw (U_{d-1} * K,) expansion,
           plus "valid" (U_{d-1}, K) and the raw ring edge times
           "t_edge" (U_{d-1}, K) — both at parent granularity, exactly as
           the per-layer attention consumes them.
    """
    hops = [{"nodes": nodes, "t": t_query}]
    for _ in range(n_hops):
        prev_rows = hops[-1]["nodes"].shape[0]
        nbr, t, valid = gather_frontier(neighbors, hops[-1]["nodes"])
        kk = nbr.shape[1]
        budget = min(prev_rows, n_nodes) * kk
        hop = compact_unique(jnp.maximum(nbr, 0).reshape(-1),
                             t.reshape(-1), budget)
        hop["valid"] = valid
        hop["t_edge"] = t
        hops.append(hop)
    return hops


def frontier_dedup_stats(neighbors, nodes, t_query, n_hops: int,
                         n_nodes: int) -> dict:
    """Host-side dedup-ratio probe for benchmark metadata: per hop the raw
    expansion size, the static unique budget, and the measured distinct-key
    count. Ratios < 1.0 mean the compacted path does less work."""
    hops = expand_frontiers_unique(neighbors, nodes, t_query, n_hops,
                                   n_nodes)
    raw = [int(h["inverse"].shape[0]) for h in hops[1:]]
    budget = [int(h["nodes"].shape[0]) for h in hops[1:]]
    uniq = [int(h["n_unique"]) for h in hops[1:]]
    tot = max(sum(raw), 1)
    return {"raw_rows": raw, "budget_rows": budget, "unique_rows": uniq,
            "budget_ratio": sum(budget) / tot,
            "measured_ratio": sum(uniq) / tot}


def expand_frontiers(neighbors, nodes, t_query, n_hops: int):
    """Recursive k-hop frontier expansion with STATIC (M * K**d,) shapes.

    hop d of the returned list describes the depth-d frontier:
      {"nodes": (M*K**d,) int32 (empty slots clamped to 0),
       "t":     (M*K**d,) fp32 query time of each frontier entry,
       "valid": (M*K**(d-1), K) bool — only for d >= 1}

    hop 0 is the seed set at the caller's query times; hop d>0 entries carry
    the ring-buffer edge time of the interaction that made them a neighbour,
    which is the query time for the next-deeper recursion (the TGN recursive
    embedding semantics, docs/DESIGN.md §Embedding stack). Everything is a
    fixed-shape gather, so the whole expansion stays jittable.
    """
    hops = [{"nodes": nodes, "t": t_query}]
    for _ in range(n_hops):
        nbr, t, valid = gather_frontier(neighbors, hops[-1]["nodes"])
        hops.append({"nodes": jnp.maximum(nbr, 0).reshape(-1),
                     "t": t.reshape(-1), "valid": valid})
    return hops
