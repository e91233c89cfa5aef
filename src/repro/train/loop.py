"""MDGNN training loop (Alg. 1 standard / Alg. 2 PRES).

Lag-one scheme: temporal batch B_{i-1} updates the memory; embeddings then
predict batch B_i (positives + sampled negatives). With PRES enabled the
memory measurement is fused with the GMM prediction (Sec. 5.1) and the
memory-coherence smoothing term (Eq. 10) is added to the loss.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batching, coherence, pres
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.train import annotate
from repro.graph.events import EventBatch, EventStream
from repro.graph.negatives import NegativeDraw, split_and_sample
from repro.models import mdgnn, modules
from repro.models.mdgnn import MDGNNConfig, MemoryState
from repro.utils import metrics as metrics_lib


def _pres_scale_and_ids(cfg, info):
    """Eq. 7 extrapolation scale + tracker ids for the touched occurrences.

    Scale: "count" extrapolates by the node's pending-event count in the
    batch — the number of sequential GRU transitions flattened into one by
    batch processing. MDGNN memory moves per EVENT, not per unit time, so
    this directly reconstructs the missed accumulation (docs/EXPERIMENTS.md
    §Paper-validation compares it against the paper-literal "time" scale)."""
    if cfg.pres_scale == "count":
        counts = jax.ops.segment_sum(
            info["mask"].astype(jnp.float32),
            jnp.where(info["mask"], info["nodes"], cfg.n_nodes),
            num_segments=cfg.n_nodes + 1)[:-1]
        scale = counts[info["nodes"]]
    else:  # "time" — paper-literal (t2 - t1)
        scale = jnp.maximum(info["t_now"] - info["t_prev"], 0.0)
    # Sec. 5.3 anchor-set approximation: GMM trackers live in hash buckets
    pres_ids = (info["nodes"] % cfg.pres_buckets if cfg.pres_buckets
                else info["nodes"])
    return scale, pres_ids


def _apply_pres(params, cfg, mem2, info, pres_state):
    """Fuse the measured memory rows with the GMM prediction and write the
    fused rows back into the table. Returns (mem_state, fused_rows, deltas).

    With cfg.use_kernels the predict -> correct -> delta-rate elementwise
    chain runs in the registered Pallas kernel "pres_filter" (one VMEM tile
    pass instead of ~6 HBM round trips); the GMM mixture-mean gather stays
    in XLA (docs/KERNELS.md §Boundary)."""
    scale, pres_ids = _pres_scale_and_ids(cfg, info)
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        dmean = pres.mixture_mean(pres_state, pres_ids)
        gamma = jax.nn.sigmoid(params["pres"]["gamma_logit"])
        fused, delta = kops.pres_filter(
            info["s_prev"], info["s_meas"], dmean, scale, gamma,
            clip=cfg.pres_clip, delta_mode=cfg.delta_mode,
            mode=cfg.kernels_mode)
    else:
        s_pred = pres.predict(pres_state, info["s_prev"], scale, pres_ids,
                              clip=cfg.pres_clip)
        fused = pres.correct(params["pres"], s_pred, info["s_meas"])
        # deltas are tracked per unit of `scale` so Eq. 7's extrapolation is
        # dimensionally consistent in either mode
        if cfg.delta_mode == "innovation":
            delta = (fused - s_pred) / jnp.maximum(scale, 1.0)[:, None]
        else:  # "transition" (Alg. 2): total memory movement per unit scale
            delta = (fused - info["s_prev"]) / jnp.maximum(scale, 1.0)[:, None]
    fused = annotate.compact(fused)   # compact-update boundary (see annotate)
    write_idx = jnp.where(info["selected"], info["nodes"], cfg.n_nodes)
    table = mdgnn.scatter_rows(mem2.mem, write_idx, fused)
    return MemoryState(mem=table, last_update=mem2.last_update), fused, delta


def fused_memory_path(cfg: MDGNNConfig, gru_fn=None) -> bool:
    """Whether the MEMORY stage runs as the fused "memory_update_table"
    kernel: with kernels and PRES on, for either memory cell (the kernel
    takes the cell as a static choice), unless the caller overrode the
    memory cell with a `gru_fn` of its own. Shared by the single-device
    and the sharded (repro.train.routing) stages."""
    return (cfg.use_kernels and cfg.use_pres
            and gru_fn in (None, modules.kernel_memory_cell(cfg)))


def _fused_memory_update(params, cfg, state, prev_batch: EventBatch):
    """The whole memory-maintenance step in ONE fused pass over the touched
    rows (registry kernel "memory_update_table", in its form for
    cfg.memory_cell): the memory-row gather, the GRU gates or the RNN
    cell, Eq. 7 predict, Eq. 8 correct, the delta-rate statistic
    AND the table/timestamp scatter-back, per occurrence, through an
    aliased (N, D) table (docs/KERNELS.md §memory_update_table). Only the
    GMM mixture-mean gather stays outside.

    The occurrences are processed in mdgnn.occurrence_order — grouped by
    node, each node's selected (written) occurrence last — which is the
    kernel's hazard-freedom precondition; the (M, D) per-occurrence outputs
    are inverse-permuted back so info/fused/delta line up with the batch
    order every caller sees.

    Returns (mem_state, info, fused, delta) matching
    mdgnn.memory_update + _apply_pres numerics bit-for-bit in fp32."""
    from repro.kernels import ops as kops
    mem = state["memory"]
    nodes, times, msgs, mask, selected, h_prev = mdgnn.memory_inputs(
        params, cfg, mem, prev_batch)
    # compact-update boundary (repro.train.annotate), as in memory_update
    times = annotate.compact(times)
    selected = annotate.compact(selected)
    nodes = annotate.compact(nodes)
    info = {"nodes": nodes, "selected": selected, "mask": mask,
            "s_prev": h_prev, "t_prev": mem.last_update[nodes],
            "t_now": times, "msgs": msgs}
    scale, pres_ids = _pres_scale_and_ids(cfg, info)
    dmean = pres.mixture_mean(state["pres"], pres_ids)
    gamma = jax.nn.sigmoid(params["pres"]["gamma_logit"])
    order = mdgnn.occurrence_order(nodes, times, mask)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    # drop-slot rows (one wider than scatter_rows): N = masked-write dump,
    # N + 1 = all-zeros masked-read source
    gidx = jnp.where(mask, nodes, cfg.n_nodes + 1)[order].astype(jnp.int32)
    widx = jnp.where(selected, nodes, cfg.n_nodes)[order].astype(jnp.int32)
    new_mem, new_t, s_meas, fused, delta = kops.memory_update_table(
        mem.mem, mem.last_update, msgs[order], gidx, widx, times[order],
        params["mem"]["w"], params["mem"]["u"], params["mem"]["b"],
        dmean[order], scale[order], gamma,
        clip=cfg.pres_clip, delta_mode=cfg.delta_mode, cell=cfg.memory_cell,
        mode=cfg.kernels_mode)
    # same compact-update boundary the cell path puts on its new_rows
    info["s_meas"] = annotate.compact(s_meas[inv])
    fused = annotate.compact(fused[inv])
    delta = delta[inv]
    return (MemoryState(mem=new_mem, last_update=new_t), info, fused, delta)


def memory_and_pres(params, cfg: MDGNNConfig, state, prev_batch: EventBatch,
                    gru_fn=None):
    """MEMORY stage + PRES fusion, shared by the sequential, eval and
    pipelined steps, with kernel routing (docs/KERNELS.md §Dispatch):

    * use_kernels + PRES        -> the fused "memory_update_table"
                                   kernel, GRU or RNN form
    * use_kernels, no PRES      -> "gru_cell" (via gru_fn) for the GRU,
                                   the jnp cell for the RNN
    * no kernels                -> pure-jnp cell + pres.predict/correct

    ("pres_filter" stays for a caller that overrides the cell with a
    gru_fn of its own under PRES.)

    Returns (mem_state, info, fused_rows, deltas); without PRES the fused
    rows are the raw measurements and the deltas are zero.

    An explicitly overridden memory cell (gru_fn other than the registry
    default) suppresses the fused path — the caller asked for that exact
    cell to run.

    With cfg.n_shards > 1 the memory/PRES tables are mesh-sharded and the
    whole stage runs through the cross-shard routing protocol
    (repro.train.routing, docs/DISTRIBUTED.md) — same contract, with
    info additionally carrying "route_overflow"."""
    if cfg.n_shards > 1:
        from repro.train import routing
        return routing.sharded_memory_and_pres(params, cfg, state,
                                               prev_batch, gru_fn=gru_fn)
    if fused_memory_path(cfg, gru_fn):
        return _fused_memory_update(params, cfg, state, prev_batch)
    mem2, info = mdgnn.memory_update(params, cfg, state["memory"],
                                     prev_batch, gru_fn=gru_fn,
                                     defer_write=cfg.use_pres)
    fused = info["s_meas"]
    delta = jnp.zeros_like(fused)
    if cfg.use_pres:
        mem2, fused, delta = _apply_pres(params, cfg, mem2, info,
                                         state["pres"])
    return mem2, info, fused, delta


def endpoint_logits(params, cfg: MDGNNConfig, state2, pos: EventBatch,
                    neg: EventBatch):
    """Link-prediction logits for a positive + negative batch.

    One batched embedding call for all four endpoint sets: one table
    gather -> ONE cotangent partial per table in the backward pass,
    instead of 4x2 table-sized combines (docs/EXPERIMENTS.md §Perf iter. 7).
    Shared by the sequential step, the eval step, and the pipelined step
    (repro.train.pipeline), which passes a staleness-filled memory view."""
    h = mdgnn.embed_nodes(
        params, cfg, state2,
        jnp.concatenate([pos.src, pos.dst, neg.src, neg.dst]),
        jnp.concatenate([pos.t, pos.t, neg.t, neg.t]))
    b = pos.src.shape[0]
    h_src_p, h_dst_p, h_src_n, h_dst_n = (
        h[:b], h[b:2 * b], h[2 * b:3 * b], h[3 * b:])
    logit_p = mdgnn.link_logits(params, h_src_p, h_dst_p)
    logit_n = mdgnn.link_logits(params, h_src_n, h_dst_n)
    return logit_p, logit_n


def link_bce(logit_p, logit_n, pos_mask, neg_mask):
    """Masked mean binary cross-entropy over positive/negative logits."""
    bce_p = jnp.sum(jax.nn.softplus(-logit_p) * pos_mask)
    bce_n = jnp.sum(jax.nn.softplus(logit_n) * neg_mask)
    denom = jnp.maximum(jnp.sum(pos_mask) + jnp.sum(neg_mask), 1.0)
    return (bce_p + bce_n) / denom


def maintain_state(cfg: MDGNNConfig, params, state2, aux,
                   prev_batch: EventBatch):
    """Non-differentiable post-step state maintenance: PRES tracker update,
    neighbour ring buffers, APAN mailbox. Shared by the sequential and the
    pipelined train steps. With cfg.n_shards > 1 every table updates
    owner-locally on its shard (repro.train.routing). Runs under the
    `maintain_state` stage scope, so its device time is attributable in a
    profiler trace."""
    with obs_trace.stage("maintain_state"):
        return _maintain_state(cfg, params, state2, aux, prev_batch)


def _maintain_state(cfg: MDGNNConfig, params, state2, aux,
                    prev_batch: EventBatch):
    if cfg.n_shards > 1:
        from repro.train import routing
        return routing.sharded_maintain_state(cfg, params, state2, aux,
                                              prev_batch)
    state2 = jax.lax.stop_gradient(state2)
    if cfg.use_pres:
        track_ids = (aux["info_nodes"] % cfg.pres_buckets
                     if cfg.pres_buckets else aux["info_nodes"])
        new_pres = pres.update_trackers(
            state2["pres"], track_ids, aux["delta"],
            jnp.zeros_like(aux["info_nodes"]),
            aux["info_selected"] & aux["info_mask"])
        state2 = dict(state2, pres=new_pres)
    state2 = dict(state2, neighbors=jax.lax.stop_gradient(
        batching.update_neighbors(state2["neighbors"], prev_batch)))
    if cfg.variant == "apan":
        nodes, times, msgs, mask = mdgnn.compute_messages(
            params, cfg, state2["memory"], prev_batch)
        state2 = dict(state2, mailbox=mdgnn.update_mailbox(
            cfg, state2["mailbox"], nodes,
            jax.lax.stop_gradient(msgs), times, mask))
    return state2


def _obs_step_stats(params, cfg: MDGNNConfig, info, fused, loss, pen,
                    pos: EventBatch, staleness=0.0):
    """Per-step telemetry vector, computed on device inside the jitted step
    (docs/OBSERVABILITY.md §Metrics). The PRES prediction error is recovered
    from values every engine already has in hand: Eq. 8 gives
    s_meas - s_pred = (s_meas - fused) / (1 - gamma), so the delta row norms
    cost one elementwise pass — no extra table gathers, identical in the
    jnp, fused-kernel and sharded paths."""
    written = info["selected"] & info["mask"]
    d_mean = d_max = d_cnt = 0.0
    if cfg.use_pres:
        gamma = jax.nn.sigmoid(params["pres"]["gamma_logit"])
        inv = 1.0 / jnp.maximum(1.0 - gamma, 1e-6)
        d_mean, d_max, d_cnt = obs_metrics.pres_delta_stats(
            fused, info["s_meas"], written)
        d_mean, d_max = d_mean * inv, d_max * inv
    return jax.lax.stop_gradient(obs_metrics.pack_train_obs(
        loss=loss, coherence_cos=1.0 - pen,
        pres_delta_mean=d_mean, pres_delta_max=d_max,
        pres_delta_events=d_cnt, staleness=staleness,
        events=jnp.sum(pos.mask.astype(jnp.float32))))


def step_negatives(neg, pos: EventBatch):
    """A step's negatives from either form of its negatives argument,
    chosen by pytree type at trace time (each form compiles once): an
    `EventBatch` is used as given; a `NegativeDraw` is split and sampled
    here, inside the compiled step, in the host loop's key order.
    Returns (negatives, next key — None for an `EventBatch`)."""
    if isinstance(neg, NegativeDraw):
        return split_and_sample(neg.key, pos, neg.dst[0], neg.dst[1])
    return neg, None


def make_step_body(cfg: MDGNNConfig, opt, gru_fn=None):
    """Un-jitted train-step body, shared by every trainer that runs the
    lag-one recurrence: the sequential jitted step below, the scan-compiled
    macro-batch engine (repro.train.scan runs this exact body under
    jax.lax.scan), and the distributed specs (repro.train.distributed
    traces it with the annotate hooks installed).

    Signature: (params, opt_state, state, prev_batch, pos, neg)
            -> (params, opt_state, state, metrics).
    `neg` is an `EventBatch` of negatives or a `NegativeDraw`, which the
    step samples from itself (`step_negatives`); it then returns the next
    key as metrics["neg_key"]."""
    if gru_fn is None:
        gru_fn = modules.kernel_memory_cell(cfg)

    def loss_and_state(params, state, prev_batch: EventBatch,
                       pos: EventBatch, neg: EventBatch):
        with obs_trace.stage("memory_update"):
            mem2, info, fused, delta = memory_and_pres(
                params, cfg, state, prev_batch, gru_fn=gru_fn)
        state2 = dict(state, memory=mem2)
        # ------------------------------------------------ link prediction --
        # sharded runs: the (unchanged) embedding stack reads a replicated
        # natural-layout view — one all-gather, exact scatter transpose
        if cfg.n_shards > 1:
            from repro.train import routing
            embed_state = routing.natural_state_view(cfg, state2)
        else:
            embed_state = state2
        with obs_trace.stage("embed"):
            logit_p, logit_n = endpoint_logits(params, cfg, embed_state,
                                               pos, neg)
        with obs_trace.stage("loss"):
            loss = link_bce(logit_p, logit_n, pos.mask, neg.mask)
            # --------------------------------------- coherence smoothing ---
            pen = coherence.coherence_penalty(
                info["s_prev"], fused, mask=info["selected"] & info["mask"])
            use_smooth = (cfg.use_smoothing if cfg.use_smoothing is not None
                          else cfg.use_pres)
            if use_smooth and cfg.beta:
                loss = loss + cfg.beta * pen
        aux = {
            "logit_p": logit_p, "logit_n": logit_n,
            "coherence_penalty": pen,
            "delta": jax.lax.stop_gradient(delta),
            "info_nodes": info["nodes"], "info_selected": info["selected"],
            "info_mask": info["mask"],
        }
        if "route_overflow" in info:
            aux["route_overflow"] = info["route_overflow"]
        if cfg.obs_metrics:
            aux["obs"] = _obs_step_stats(params, cfg, info, fused, loss, pen,
                                         pos)
            if "route_overflow_shards" in info:
                aux["route_overflow_shards"] = jax.lax.stop_gradient(
                    info["route_overflow_shards"])
        return loss, (state2, aux)

    def train_step(params, opt_state, state, prev_batch, pos, neg):
        neg, next_key = step_negatives(neg, pos)
        (loss, (state2, aux)), grads = jax.value_and_grad(
            loss_and_state, has_aux=True)(params, state, prev_batch, pos, neg)
        with obs_trace.stage("apply"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                  params, updates)
        # ------------------------- non-differentiable state maintenance ----
        state2 = maintain_state(cfg, params, state2, aux, prev_batch)
        metrics = {"loss": loss, "coherence_penalty": aux["coherence_penalty"],
                   "logit_p": aux["logit_p"], "logit_n": aux["logit_n"]}
        if "route_overflow" in aux:
            # budget-masked valid rows this step (docs/DISTRIBUTED.md
            # §Budget) — zero unless cfg.shard_budget was tightened
            metrics["route_overflow"] = aux["route_overflow"]
        for k in ("obs", "route_overflow_shards"):
            if k in aux:
                metrics[k] = aux[k]
        if next_key is not None:
            metrics["neg_key"] = next_key
        return params, opt_state, state2, metrics

    return train_step


def make_train_step(cfg: MDGNNConfig, opt, gru_fn=None):
    """Returns a jitted train_step closure.

    cfg.use_kernels routes the FULL memory-maintenance path plus the
    embedding attention through the registered Pallas kernels
    (docs/KERNELS.md): under PRES the whole update fuses into the
    "memory_update_table" kernel (GRU or RNN form); without PRES the GRU
    cell routes through "gru_cell" (resolved by
    modules.kernel_memory_cell), and the neighbour attention resolves inside
    embed_nodes (docs/DESIGN.md §Embedding stack). Pass gru_fn explicitly
    to override the memory cell only.

    The optimizer state and the model state (memory table, neighbour ring
    buffers, PRES trackers, APAN mailbox) are DONATED: XLA aliases the
    (N, D) buffers in place instead of allocating a fresh table per step
    (docs/SCAN.md §Donation). Callers must not reuse the opt_state/state
    they passed in — only the returned ones.

    With cfg.n_shards > 1 the returned step additionally replicates the
    per-step host inputs (batches, negatives or their draw) onto the mesh
    before the jitted call — the carried params/opt_state/state are
    expected already placed by routing.replicate/shard_state
    (docs/DISTRIBUTED.md)."""
    step = jax.jit(make_step_body(cfg, opt, gru_fn=gru_fn),
                   donate_argnums=(1, 2))
    return _replicating_inputs(cfg, step, n_carry=3)


def _replicating_inputs(cfg: MDGNNConfig, step, n_carry: int):
    """Wrap a jitted step so the non-carry (host-produced) arguments are
    replicated onto the mesh — mixing freshly-sampled single-device arrays
    with mesh-sharded carries in one jit is a placement error."""
    if cfg.n_shards <= 1:
        return step
    from repro.train import routing

    @functools.wraps(step)
    def wrapped(*args):
        carry, rest = args[:n_carry], args[n_carry:]
        return step(*carry, *routing.replicate(rest, cfg.n_shards))

    return wrapped


def make_eval_step(cfg: MDGNNConfig):
    """Jitted fold-then-score step: (params, state, prev_batch, pos, neg)
    -> (state, logit_p, logit_n). Given a `NegativeDraw` as `neg` it samples
    in the step, like the train step, and returns the next key as a fourth
    output."""
    gru_fn = modules.kernel_memory_cell(cfg)

    def eval_step(params, state, prev_batch, pos, neg):
        neg, next_key = step_negatives(neg, pos)
        out = fold_and_score(params, state, prev_batch, pos, neg)
        return out if next_key is None else out + (next_key,)

    def fold_and_score(params, state, prev_batch, pos, neg):
        mem2, _, _, _ = memory_and_pres(params, cfg, state, prev_batch,
                                        gru_fn=gru_fn)
        state2 = dict(state, memory=mem2)
        if cfg.n_shards > 1:
            from repro.train import routing
            state2 = dict(state2, neighbors=routing.sharded_neighbor_update(
                cfg, state2["neighbors"], prev_batch))
            embed_state = routing.natural_state_view(cfg, state2)
            if cfg.variant == "apan":
                nodes, times, msgs, mask = mdgnn.compute_messages(
                    params, cfg, embed_state["memory"], prev_batch)
                state2 = dict(state2, mailbox=routing.sharded_mailbox_update(
                    cfg, state2["mailbox"], nodes, msgs, times, mask))
                embed_state = dict(embed_state,
                                   mailbox=routing.natural_component_view(
                                       cfg, state2["mailbox"], "mailbox"))
            logit_p, logit_n = endpoint_logits(params, cfg, embed_state,
                                               pos, neg)
            return state2, logit_p, logit_n
        state2 = dict(state2, neighbors=batching.update_neighbors(
            state2["neighbors"], prev_batch))
        if cfg.variant == "apan":
            nodes, times, msgs, mask = mdgnn.compute_messages(
                params, cfg, state2["memory"], prev_batch)
            state2 = dict(state2, mailbox=mdgnn.update_mailbox(
                cfg, state2["mailbox"], nodes, msgs, times, mask))
        logit_p, logit_n = endpoint_logits(params, cfg, state2, pos, neg)
        return state2, logit_p, logit_n

    return _replicating_inputs(cfg, jax.jit(eval_step), n_carry=2)


# How many steps behind its dispatch a step's outputs are pulled: step i's
# `train.pull` converts step i - PULL_LAG's outputs to numpy, so the chip
# keeps the steps in between queued while the host waits on the oldest.
# Chosen from chip runs of 1 to 4 (PERF.md §6).
PULL_LAG = 2


class _LaggedPull:
    """The epoch's per-step outputs on their way to the host. `push` starts
    a step's host copies (`copy_to_host_async`) and converts the oldest
    queued step to numpy once more than `PULL_LAG` are queued; `drain`
    converts the rest and returns every step's numpy tuple, oldest first.
    Nothing runs on the device: no program, and no wait but for the oldest
    step."""

    def __init__(self):
        self.queued = []
        self.pulled = []

    def push(self, outs: tuple) -> None:
        for x in outs:
            x.copy_to_host_async()
        self.queued.append(outs)
        if len(self.queued) > PULL_LAG:
            self._pull_oldest()

    def drain(self) -> list:
        while self.queued:
            self._pull_oldest()
        return self.pulled

    def _pull_oldest(self) -> None:
        self.pulled.append(tuple(np.asarray(x)
                                 for x in self.queued.pop(0)))


@dataclasses.dataclass
class EpochResult:
    ap: float
    loss: float
    seconds: float
    aps: list
    # sharded runs (cfg.n_shards > 1): epoch total of budget-masked routed
    # rows — nonzero only when cfg.shard_budget was tightened below the
    # overflow-free default (docs/DISTRIBUTED.md §Budget)
    route_overflow: int = 0
    # cfg.obs_metrics runs: per-step telemetry series fetched in the
    # epoch's single flush — {"series": {field: [floats]}, "steps": int,
    # "route_overflow_shards": [ints] (sharded only)} (obs.metrics)
    obs: dict | None = None


def run_epoch(params, opt_state, state, batches, cfg: MDGNNConfig,
              train_step, key, dst_range, collect_logits=False):
    """One training epoch over the temporal batches (lag-one).

    `batches` may be a materialized list OR a lazy/prefetching iterator
    (`EventStream.prefetch_batches`) — the driver consumes it pairwise.
    Each step's loss and logits are pulled to numpy `PULL_LAG` steps after
    its dispatch (`_LaggedPull`), so the host never waits on the step it
    just dispatched and device memory stays bounded at a few steps' worth;
    the epoch end converts the last ones.

    The negatives are drawn inside the compiled step (`NegativeDraw`): the
    host forwards the key the previous step returned and the destination
    bounds, put on the device once per epoch, so no eager program runs per
    step besides the step itself.

    Host spans (obs.trace, recorded when enabled) tile each step: the
    draw's forwarding (`train.sample`), the step call until it returns
    (`train.dispatch`), the lagged pull (`train.pull`: step i - PULL_LAG's
    outputs), each carrying the step index; then the epoch-end work
    (`train.epoch_end`: the last PULL_LAG pulls, `EpochObs.finish`, AP)."""
    t0 = time.perf_counter()
    pull = _LaggedPull()
    obs = obs_metrics.EpochObs()
    draw = NegativeDraw.start(key, dst_range)
    it = iter(batches)
    try:
        prev_batch = next(it)
        for i, batch in enumerate(it):
            with obs_trace.span("train.sample", step=i):
                draw = dataclasses.replace(draw, key=key)
            with obs_trace.span("train.dispatch", step=i):
                params, opt_state, state, m = train_step(
                    params, opt_state, state, prev_batch, batch, draw)
                key = m.pop("neg_key")
            with obs_trace.span("train.pull", step=i):
                pull.push((m["loss"], m["logit_p"], m["logit_n"]))
                obs.step(m)                            # device values only
            prev_batch = batch
    finally:
        # stop a PrefetchIterator's producer thread if the epoch aborts
        close = getattr(it, "close", None)
        if close is not None:
            close()
    with obs_trace.span("train.epoch_end"):
        steps = pull.drain()
        losses = [float(loss) for loss, _, _ in steps]
        pos_all = [p for _, p, _ in steps]
        neg_all = [n for _, _, n in steps]
        route_overflow, obs_out = obs.finish()         # one batched fetch
        ap = metrics_lib.average_precision(np.concatenate(pos_all),
                                           np.concatenate(neg_all))
        aps = [metrics_lib.average_precision(p, n)
               for p, n in zip(pos_all, neg_all)] if collect_logits else []
    dt = time.perf_counter() - t0
    return params, opt_state, state, EpochResult(
        ap, float(np.mean(losses)), dt, aps,
        route_overflow=route_overflow, obs=obs_out)


def evaluate(params, state, batches, cfg: MDGNNConfig, eval_step, key, dst_range):
    """Evaluation pass; `batches` may be a list or a (prefetching) iterator.
    Negatives are drawn inside the step, the logits are pulled
    `PULL_LAG` steps late and host spans tile each step, as in
    `run_epoch`."""
    pull = _LaggedPull()
    draw = NegativeDraw.start(key, dst_range)
    it = iter(batches)
    try:
        prev_batch = next(it)
        for i, batch in enumerate(it):
            with obs_trace.span("train.sample", step=i):
                draw = dataclasses.replace(draw, key=key)
            with obs_trace.span("train.dispatch", step=i):
                state, lp, ln, key = eval_step(params, state, prev_batch,
                                               batch, draw)
            with obs_trace.span("train.pull", step=i):
                pull.push((lp, ln))
            prev_batch = batch
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()
    with obs_trace.span("train.epoch_end"):
        steps = pull.drain()
        pos = np.concatenate([p for p, _ in steps])
        neg = np.concatenate([n for _, n in steps])
        ap = metrics_lib.average_precision(pos, neg)
        auc = metrics_lib.roc_auc(pos, neg)
    return state, ap, auc
