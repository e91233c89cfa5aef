"""Cross-shard event routing for memory-parallel training (docs/DISTRIBUTED.md).

The memory/neighbour/PRES/mailbox tables are partitioned across a real
1-D `jax.sharding.Mesh` by `node_id % n_shards` (the DistTGL memory-parallel
direction, PAPERS.md arXiv:2307.07649). Because a mod-partition is not a
contiguous row range, the tables are stored in a *shard-major permuted
physical layout*: node v lives at physical row

    owner(v) * rows_per_shard + v // n_shards,   owner(v) = v % n_shards

padded to `rows_per_shard = ceil(N / n_shards)` rows per shard, so the
mod-partition becomes a plain contiguous `NamedSharding(mesh, P("shard"))`
on axis 0. `shard_state`/`unshard_state` convert whole model states between
the natural and the permuted layout at setup/teardown; `natural_state_view`
builds a replicated natural-layout *read view* inside jit (a static-index
gather the SPMD partitioner lowers to one all-gather), so the embedding
stack and every decoder run unchanged.

The per-batch protocol (`sharded_memory_and_pres`) is ONE shard_map region:

1. request gather — each shard all-gathers the batch's touched node ids and
   answers for the rows it owns (masked contribution + psum), yielding the
   pre-update memory rows, last-update times and GMM mixture-mean deltas
   for every occurrence;
2. MESSAGE stage — event-sharded: each shard computes messages for its
   contiguous slice of the 2b endpoint occurrences;
3. route — occurrences are bucketed by owner shard into a flat
   (n_shards * budget, ...) send buffer (`bucket_plan`: stable
   per-destination arrival ranks, the same pad-invariant machinery as
   `batching.ring_buffer_append`) and delivered with a SINGLE
   `lax.all_to_all`; rows past the static per-lane `budget` are masked out
   and COUNTED — the overflow count is summed across shards and surfaced
   in the step metrics (`route_overflow`), never silently dropped. The
   default budget (occurrences-per-shard) makes overflow impossible.
4. owner-local update — the owner sees every routed occurrence of its
   nodes, recomputes the selected-last flags / PRES extrapolation scale
   locally (identical winners: the lexsort tie-breaks on the global batch
   position), and applies the update to its table slice — through the
   SAME fused `memory_update_table` kernel as the single-device path when
   cfg.use_kernels, else the jnp cell + PRES predict/correct math;
5. unroute — per-occurrence outputs (s_meas, fused, delta, selected) take
   the reverse all_to_all back to their senders, so the loss stage sees
   them in batch order.

Everything returned by the shard_map is axis-sharded (out_specs mention
"shard"), which keeps shard_map's replication check (`check_vma`)
satisfied and gives exact collective transposes for the gradient path
(loss -> embedding view -> table scatter -> reverse route -> GRU/message
params).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import batching, pres
from repro.models import mdgnn, modules
from repro.models.mdgnn import MDGNNConfig
from repro.models.modules import MemoryState

AXIS = "shard"


# ---------------------------------------------------------------------------
# Mesh + shard-major permuted layout
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def get_mesh(n_shards: int) -> Mesh:
    """1-D device mesh over the first n_shards local devices.

    On a CPU host the mesh is emulated by setting
    XLA_FLAGS=--xla_force_host_platform_device_count=N *before* jax is
    imported (docs/DISTRIBUTED.md §Emulated mesh) — tests and fig_dist
    spawn subprocesses for exactly that reason."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"n_shards={n_shards} but only {len(devs)} device(s) visible; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} before importing jax (docs/DISTRIBUTED.md)")
    return Mesh(np.array(devs[:n_shards]), (AXIS,))


def rows_per_shard(n_rows: int, n_shards: int) -> int:
    return -(-n_rows // n_shards)


def padded_rows(n_rows: int, n_shards: int) -> int:
    return rows_per_shard(n_rows, n_shards) * n_shards


def phys_index(ids, n_rows: int, n_shards: int):
    """Natural id -> physical row in the shard-major permuted layout."""
    per = rows_per_shard(n_rows, n_shards)
    return (ids % n_shards) * per + ids // n_shards


def to_shard_layout(x, n_rows: int, n_shards: int):
    """Natural (n_rows, ...) array -> permuted+padded (padded_rows, ...)."""
    x = np.asarray(x)
    out = np.zeros((padded_rows(n_rows, n_shards),) + x.shape[1:], x.dtype)
    out[np.asarray(phys_index(np.arange(n_rows), n_rows, n_shards))] = x
    return out


def from_shard_layout(x, n_rows: int, n_shards: int):
    """Permuted+padded (padded_rows, ...) array -> natural (n_rows, ...)."""
    x = np.asarray(x)
    return x[np.asarray(phys_index(np.arange(n_rows), n_rows, n_shards))]


def _component_rows(cfg: MDGNNConfig, name: str) -> int:
    """Leading-axis row count of a state component in natural layout."""
    if name == "pres":
        return cfg.pres_buckets or cfg.n_nodes
    return cfg.n_nodes


def shard_state(cfg: MDGNNConfig, state, mesh: Mesh | None = None):
    """Host-side: natural model state -> permuted layout, placed on the mesh
    with every table row-sharded. The inverse is `unshard_state`."""
    mesh = mesh or get_mesh(cfg.n_shards)
    shd = NamedSharding(mesh, P(AXIS))
    out = {}
    for name, comp in state.items():
        n_rows = _component_rows(cfg, name)
        out[name] = jax.tree.map(
            lambda x: jax.device_put(
                to_shard_layout(x, n_rows, cfg.n_shards), shd), comp)
    return out


def unshard_state(cfg: MDGNNConfig, state):
    """Sharded permuted-layout state -> natural-layout numpy state."""
    out = {}
    for name, comp in state.items():
        n_rows = _component_rows(cfg, name)
        out[name] = jax.tree.map(
            lambda x: from_shard_layout(x, n_rows, cfg.n_shards), comp)
    return out


def replicate(tree, n_shards: int):
    """Place a pytree fully replicated on the mesh (params, opt state,
    incoming event batches — everything that is not a node table)."""
    return jax.device_put(tree, NamedSharding(get_mesh(n_shards), P()))


# ---------------------------------------------------------------------------
# Natural-layout read views (inside jit)
# ---------------------------------------------------------------------------


def replicated(cfg: MDGNNConfig, fn):
    """`fn` run whole on every shard over replicated operands — how a
    Pallas kernel, which the SPMD partitioner cannot split, reads the
    replicated natural-layout views inside a sharded step. Identity when
    cfg.n_shards == 1."""
    if cfg.n_shards <= 1:
        return fn
    return jax.shard_map(fn, mesh=get_mesh(cfg.n_shards), in_specs=P(),
                         out_specs=P())


def natural_rows(cfg: MDGNNConfig, x, n_rows: int):
    """Replicated natural-layout view of one sharded table, inside jit.

    A gather at a static permutation: the SPMD partitioner lowers it to one
    all-gather + local permute, and its transpose (scatter) is exact — the
    gradient path from the loss back into the sharded table goes through
    here for the fused rows the embedding reads."""
    idx = phys_index(jnp.arange(n_rows), n_rows, cfg.n_shards)
    return x[idx]


def natural_component_view(cfg: MDGNNConfig, comp, name: str):
    n_rows = _component_rows(cfg, name)
    return jax.tree.map(lambda x: natural_rows(cfg, x, n_rows), comp)


def natural_state_view(cfg: MDGNNConfig, state):
    """Replicated natural-layout view of the whole model state — what the
    (unchanged) embedding stack reads in place of the sharded state."""
    return {name: natural_component_view(cfg, comp, name)
            for name, comp in state.items()}


def natural_memory(cfg: MDGNNConfig, mem: MemoryState) -> MemoryState:
    return natural_component_view(cfg, mem, "memory")


# ---------------------------------------------------------------------------
# Routing plan (pure — property-tested in tests/test_routing.py)
# ---------------------------------------------------------------------------


def bucket_plan(owner, valid, n_shards: int, budget: int):
    """Per-occurrence routing plan for the flat (n_shards * budget, ...)
    send buffer.

    Returns (slot, rank, kept, overflow): `rank` is the stable arrival rank
    of each VALID occurrence within its destination lane (array order —
    the same pad-invariant stable-sort/searchsorted machinery as
    batching.ring_buffer_append, so padding rows can never perturb the
    ranks of valid ones); `kept = valid & (rank < budget)`;
    `slot = owner * budget + rank` for kept rows and the out-of-range drop
    slot otherwise; `overflow` counts the valid rows masked out by the
    budget — callers must surface it (sum(kept) + overflow == sum(valid)
    is the no-silent-truncation invariant)."""
    m = owner.shape[0]
    keys = jnp.where(valid, owner, n_shards)
    order = jnp.argsort(keys, stable=True)
    sorted_keys = keys[order]
    start = jnp.searchsorted(sorted_keys, jnp.arange(n_shards + 1))
    rank_sorted = jnp.arange(m) - start[sorted_keys]
    rank = jnp.zeros(m, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    kept = valid & (rank < budget)
    overflow = jnp.sum((valid & (rank >= budget)).astype(jnp.int32))
    slot = jnp.where(kept, owner * budget + rank, n_shards * budget)
    return slot.astype(jnp.int32), rank, kept, overflow


def bucket_scatter(x, slot, n_shards: int, budget: int, fill=0):
    """Scatter per-occurrence rows into the flat send buffer (drop-slot
    trick: index n_shards*budget falls off the end and is discarded)."""
    buf = jnp.full((n_shards * budget + 1,) + x.shape[1:], fill, x.dtype)
    return buf.at[slot].set(x.astype(buf.dtype), mode="drop")[:-1]


def bucket_gather(flat, owner, rank, budget: int, kept, fill=0):
    """Inverse of bucket_scatter on the RETURN path: read occurrence
    (owner, rank)'s row back out of a flat (n_shards * budget, ...) buffer;
    rows that were never routed (masked or overflowed) read `fill`."""
    idx = jnp.clip(owner * budget + rank, 0, flat.shape[0] - 1)
    out = flat[idx]
    keep = kept.reshape(kept.shape + (1,) * (out.ndim - 1))
    return jnp.where(keep, out, jnp.asarray(fill, out.dtype))


# ---------------------------------------------------------------------------
# The sharded MEMORY + PRES stage
# ---------------------------------------------------------------------------


def _padded_occurrences(batch, n_shards: int):
    """node_occurrences padded to a multiple of n_shards (mask=False pads)
    plus each occurrence's global batch position (the selected-flag
    tie-break the owner shard uses)."""
    nodes, times, other, feat, mask = batching.node_occurrences(batch)
    m = nodes.shape[0]
    m_pad = padded_rows(m, n_shards)

    def pad(x, fill):
        if m_pad == m:
            return x
        return jnp.concatenate(
            [x, jnp.full((m_pad - m,) + x.shape[1:], fill, x.dtype)])

    return (pad(nodes, 0), pad(times, 0.0), pad(other, 0),
            pad(feat, 0.0), pad(mask, False),
            jnp.arange(m_pad, dtype=jnp.int32), m)


def _owner_gather(table, req, me, n_shards: int):
    """Answer a replicated (R,) natural-id request vector from a local
    table slice: each shard contributes the rows it owns (zeros elsewhere)
    and a psum assembles the full (R, ...) response on every shard. One-hot
    contributions make the sum exact (0 + x == x in floating point)."""
    own = (req % n_shards) == me
    loc = jnp.where(own, req // n_shards, 0)
    rows = table[loc].astype(jnp.float32)
    keep = own.reshape(own.shape + (1,) * (rows.ndim - 1))
    return jax.lax.psum(jnp.where(keep, rows, 0.0), AXIS)


def sharded_memory_and_pres(params, cfg: MDGNNConfig, state, prev_batch,
                            gru_fn=None):
    """Drop-in replacement for loop.memory_and_pres when cfg.n_shards > 1:
    same (mem_state, info, fused, delta) contract, with the memory/PRES
    tables sharded and the touched rows delivered by the routing protocol
    in the module docstring. info additionally carries "route_overflow"
    (the all-shard sum of budget-masked valid rows this step)."""
    n = cfg.n_shards
    mesh = get_mesh(n)
    mem = state["memory"]
    n_buckets = cfg.pres_buckets or cfg.n_nodes
    nodes, times, other, feat, mask, pos, m = _padded_occurrences(
        prev_batch, n)
    m_slice = nodes.shape[0] // n                     # occurrences per shard
    budget = cfg.shard_budget or m_slice              # default: overflow-free
    from repro.train import loop
    use_fused = loop.fused_memory_path(cfg, gru_fn)
    # Per-bucket GMM mixture-mean table, elementwise over the sharded
    # trackers (stays sharded, no communication): the request gather below
    # serves dmean rows from it exactly like memory rows.
    alpha, mu, _ = state["pres"].gmm()
    mean_tab = jnp.sum(alpha[..., None] * mu, axis=1)   # (buckets_pad, D)

    def body(mem_l, lu_l, mean_l, nodes_l, times_l, other_l, feat_l, mask_l,
             pos_l, params):
        me = jax.lax.axis_index(AXIS)
        per_node = mem_l.shape[0]
        ms = nodes_l.shape[0]
        nodes_c = jnp.clip(nodes_l, 0, cfg.n_nodes - 1)
        other_c = jnp.clip(other_l, 0, cfg.n_nodes - 1)
        # ---- 1. request gather: pre-update rows for both endpoints -------
        req = jax.lax.all_gather(
            jnp.concatenate([nodes_c, other_c]), AXIS, tiled=True)
        rows = _owner_gather(mem_l, req, me, n)       # (n*2ms, D) replicated
        mine = jax.lax.dynamic_slice_in_dim(rows, me * 2 * ms, 2 * ms)
        s_self, s_other = mine[:ms], mine[ms:]
        lu_req = jax.lax.all_gather(nodes_c, AXIS, tiled=True)
        lu_rows = _owner_gather(lu_l, lu_req, me, n)
        t_prev = jax.lax.dynamic_slice_in_dim(lu_rows, me * ms, ms)
        bucket = nodes_c % n_buckets
        b_req = jax.lax.all_gather(bucket, AXIS, tiled=True)
        d_rows = _owner_gather(mean_l, b_req, me, n)
        dmean = jax.lax.dynamic_slice_in_dim(d_rows, me * ms, ms)
        # ---- 2. MESSAGE stage (event-sharded) ----------------------------
        t_enc = modules.time_encode(params["time"], times_l - t_prev)
        msgs = modules.message(params["msg"], s_self, s_other, feat_l, t_enc)
        # ---- 3. route to owners: one all_to_all --------------------------
        owner = nodes_c % n
        slot, rank, kept, overflow = bucket_plan(owner, mask_l, n, budget)

        def route(x, fill=0.0):
            return jax.lax.all_to_all(
                bucket_scatter(x, slot, n, budget, fill), AXIS, 0, 0,
                tiled=True)

        r_node = route(nodes_c, 0)
        r_valid = route(kept, False)
        r_t = route(times_l, 0.0)
        r_msg = route(msgs)
        r_dmean = route(dmean)
        r_pos = route(pos_l, 0)
        # ---- 4. owner-local update ---------------------------------------
        nb = r_node.shape[0]
        r_loc = jnp.clip(r_node // n, 0, per_node - 1)
        if cfg.aggregator == "mean":
            seg = jnp.where(r_valid, r_loc, per_node)
            summed = jax.ops.segment_sum(r_msg * r_valid[:, None], seg,
                                         num_segments=per_node + 1)
            cnt = jax.ops.segment_sum(r_valid.astype(jnp.float32), seg,
                                      num_segments=per_node + 1)
            r_msg = (summed / jnp.maximum(cnt[:, None], 1.0))[r_loc]
        # selected-last flags: same winner as the global
        # _last_occurrence_flags — the owner holds every routed occurrence
        # of its nodes, and the global batch position breaks time ties
        # exactly like the stable global lexsort does
        node_key = jnp.where(r_valid, r_loc, jnp.iinfo(jnp.int32).max)
        big_t = jnp.where(r_valid, r_t, -jnp.inf)
        order = jnp.lexsort((r_pos, big_t, node_key))
        nk_s, v_s = node_key[order], r_valid[order]
        is_last = jnp.concatenate(
            [(nk_s[1:] != nk_s[:-1]) | ~v_s[1:], jnp.ones((1,), bool)])
        selected = jnp.zeros(nb, bool).at[order].set(is_last & v_s)
        if cfg.pres_scale == "count":
            cnt_n = jax.ops.segment_sum(
                r_valid.astype(jnp.float32),
                jnp.where(r_valid, r_loc, per_node),
                num_segments=per_node + 1)[:-1]
            scale = cnt_n[r_loc]
        else:  # "time"
            scale = jnp.maximum(r_t - lu_l[r_loc], 0.0)
        gamma = jax.nn.sigmoid(params["pres"]["gamma_logit"])
        if use_fused:
            from repro.kernels import ops as kops
            # `order` already groups by node with the selected occurrence
            # last — the fused table kernel's hazard-freedom precondition
            inv = jnp.zeros_like(order).at[order].set(jnp.arange(nb))
            gidx = jnp.where(r_valid, r_loc, per_node + 1)[order]
            widx = jnp.where(selected, r_loc, per_node)[order]
            new_mem, new_lu, s_meas, fused, delta = kops.memory_update_table(
                mem_l, lu_l, r_msg[order], gidx.astype(jnp.int32),
                widx.astype(jnp.int32), r_t[order],
                params["mem"]["w"], params["mem"]["u"], params["mem"]["b"],
                r_dmean[order], scale[order], gamma,
                clip=cfg.pres_clip, delta_mode=cfg.delta_mode,
                cell=cfg.memory_cell, mode=cfg.kernels_mode)
            s_meas, fused, delta = s_meas[inv], fused[inv], delta[inv]
        else:
            _, cell = modules.MEMORY_CELLS[cfg.memory_cell]
            if gru_fn is not None and cfg.memory_cell == "gru":
                cell = gru_fn
            h_prev = mem_l[r_loc].astype(jnp.float32)
            s_meas = cell(params["mem"], r_msg, h_prev)
            if cfg.use_pres:
                s_pred = h_prev + jnp.clip(scale[:, None] * r_dmean,
                                           -cfg.pres_clip, cfg.pres_clip)
                fused = (1.0 - gamma) * s_pred + gamma * s_meas
                base = s_pred if cfg.delta_mode == "innovation" else h_prev
                delta = (fused - base) / jnp.maximum(scale, 1.0)[:, None]
            else:
                fused, delta = s_meas, jnp.zeros_like(s_meas)
            widx = jnp.where(selected, r_loc, per_node)
            new_mem = mdgnn.scatter_rows(mem_l, widx, fused)
            new_lu = mdgnn.scatter_rows(lu_l, widx, r_t)
        # ---- 5. unroute per-occurrence outputs back to the senders -------
        def unroute(x, fill=0.0):
            back = jax.lax.all_to_all(x, AXIS, 0, 0, tiled=True)
            return bucket_gather(back, owner, rank, budget, kept, fill)

        out_s_meas = unroute(s_meas)
        out_fused = unroute(fused)
        out_delta = unroute(delta)
        out_sel = unroute(selected, False)
        if cfg.aggregator == "mean":
            # match memory_update's info contract: each VALID occurrence
            # carries its node's mean message (masked rows read 0 here —
            # nothing downstream consumes them)
            msgs = unroute(r_msg)
        return (new_mem, new_lu, out_s_meas, out_fused, out_delta, out_sel,
                s_self, t_prev, msgs,
                jnp.full((1,), overflow, jnp.int32))

    spec_n = P(AXIS)
    p_specs = jax.tree.map(lambda _: P(), params)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None), spec_n, P(AXIS, None), spec_n, spec_n,
                  spec_n, P(AXIS, None), spec_n, spec_n, p_specs),
        out_specs=(P(AXIS, None), spec_n, P(AXIS, None), P(AXIS, None),
                   P(AXIS, None), spec_n, P(AXIS, None), spec_n,
                   P(AXIS, None), spec_n),
    )(mem.mem, mem.last_update, mean_tab, nodes, times, other, feat, mask,
      pos, params)
    (new_mem, new_lu, s_meas, fused, delta, sel, s_prev, t_prev, msgs,
     overflow) = out
    info = {"nodes": nodes[:m], "selected": sel[:m], "mask": mask[:m],
            "s_prev": s_prev[:m], "s_meas": s_meas[:m],
            "t_prev": t_prev[:m], "t_now": times[:m], "msgs": msgs[:m],
            "route_overflow": jnp.sum(overflow),
            # per-shard counts (n_shards,) — the telemetry layer surfaces
            # these as the shard-imbalance signal (docs/OBSERVABILITY.md);
            # step bodies thread them out only when cfg.obs_metrics
            "route_overflow_shards": overflow}
    return (MemoryState(mem=new_mem, last_update=new_lu), info,
            fused[:m], delta[:m])


# ---------------------------------------------------------------------------
# Sharded non-differentiable state maintenance
# ---------------------------------------------------------------------------


def _ring_specs(bufs):
    return jax.tree.map(lambda x: P(AXIS, *([None] * (x.ndim - 1))), bufs)


def sharded_ring_append(cfg: MDGNNConfig, bufs, ptr, nodes, values, mask):
    """Owner-local ring-buffer append: every shard sees the full replicated
    occurrence arrays and appends only the rows it owns (ownership folded
    into the mask). Per-node ranks match the global ones because the stable
    sort preserves the relative order of same-node valid occurrences —
    the pad-invariance guarantee ring_buffer_append already provides."""
    n = cfg.n_shards
    mesh = get_mesh(n)

    def body(bufs_l, ptr_l, nodes, values, mask):
        me = jax.lax.axis_index(AXIS)
        nodes_c = jnp.clip(nodes, 0, cfg.n_nodes - 1)
        own = (nodes_c % n) == me
        return batching.ring_buffer_append(
            bufs_l, ptr_l, nodes_c // n, values, mask & own)

    v_specs = jax.tree.map(lambda _: P(), values)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(_ring_specs(bufs), P(AXIS), P(), v_specs, P()),
        out_specs=(_ring_specs(bufs), P(AXIS)),
    )(bufs, ptr, nodes, values, mask)


def sharded_neighbor_update(cfg: MDGNNConfig, neighbors, batch):
    nodes, times, other, _, mask = batching.node_occurrences(batch)
    bufs, ptr = sharded_ring_append(
        cfg, {"nbr": neighbors["nbr"], "t": neighbors["t"]},
        neighbors["ptr"], nodes, {"nbr": other, "t": times}, mask)
    return {"nbr": bufs["nbr"], "t": bufs["t"], "ptr": ptr}


def sharded_mailbox_update(cfg: MDGNNConfig, mailbox, nodes, msgs, times,
                           mask):
    bufs, ptr = sharded_ring_append(
        cfg, {"msg": mailbox["msg"], "t": mailbox["t"]}, mailbox["ptr"],
        nodes, {"msg": msgs, "t": times}, mask)
    return {"msg": bufs["msg"], "t": bufs["t"], "ptr": ptr}


def sharded_tracker_update(cfg: MDGNNConfig, pres_state, track_ids, delta,
                           mask):
    """Owner-local Eq. 9 tracker update over the sharded GMM tables. The
    per-bucket sums accumulate the same values in the same array order as
    the single-device segment_sum, so the update is bitwise-stable."""
    n = cfg.n_shards
    n_buckets = cfg.pres_buckets or cfg.n_nodes
    mesh = get_mesh(n)

    def body(pn, pxi, ppsi, ids, delta, mask):
        me = jax.lax.axis_index(AXIS)
        ids_c = jnp.clip(ids, 0, n_buckets - 1)
        own = (ids_c % n) == me
        st = pres.update_trackers(
            pres.PresState(n=pn, xi=pxi, psi=ppsi), ids_c // n, delta,
            jnp.zeros_like(ids_c), mask & own)
        return st.n, st.xi, st.psi

    pn, pxi, ppsi = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None, None), P(AXIS, None, None),
                  P(), P(), P()),
        out_specs=(P(AXIS, None), P(AXIS, None, None), P(AXIS, None, None)),
    )(pres_state.n, pres_state.xi, pres_state.psi, track_ids, delta, mask)
    return pres.PresState(n=pn, xi=pxi, psi=ppsi)


def sharded_maintain_state(cfg: MDGNNConfig, params, state2, aux, prev_batch,
                           mem_view: MemoryState | None = None):
    """Sharded counterpart of loop.maintain_state: PRES trackers, neighbour
    rings and the APAN mailbox all update owner-locally from the replicated
    occurrence arrays — no routing needed, the ownership mask plus the
    pad-invariant ring fold deliver per-node parity. `mem_view` (a natural-
    layout view of the LIVE post-update memory) is only needed for the APAN
    message recompute and is gathered here when not supplied."""
    state2 = jax.lax.stop_gradient(state2)
    if cfg.use_pres:
        track_ids = (aux["info_nodes"] % cfg.pres_buckets
                     if cfg.pres_buckets else aux["info_nodes"])
        state2 = dict(state2, pres=sharded_tracker_update(
            cfg, state2["pres"], track_ids, aux["delta"],
            aux["info_selected"] & aux["info_mask"]))
    state2 = dict(state2, neighbors=sharded_neighbor_update(
        cfg, state2["neighbors"], prev_batch))
    if cfg.variant == "apan":
        if mem_view is None:
            mem_view = natural_memory(cfg, state2["memory"])
        nodes, times, msgs, mask = mdgnn.compute_messages(
            params, cfg, mem_view, prev_batch)
        state2 = dict(state2, mailbox=sharded_mailbox_update(
            cfg, state2["mailbox"], nodes, jax.lax.stop_gradient(msgs),
            times, mask))
    return state2
