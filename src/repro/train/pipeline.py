"""Staleness-aware pipelined training schedule (docs/PIPELINE.md).

The sequential loop (repro.train.loop, Alg. 1/2) serialises
sample -> memory update -> embed -> loss per temporal batch, leaving the
accelerator idle during host-side batch prep and forcing every embedding
to wait on the immediately preceding memory write. Following the
MSPipe/DistTGL observation that the memory module tolerates *bounded*
staleness, this module decouples the two stages:

* the MEMORY stage keeps the live table exactly as in the sequential loop
  (every batch's writes land immediately, PRES fusion included);
* the EMBEDDING stage reads a double-buffered *snapshot* of the table that
  is refreshed every `cfg.pipeline_depth` steps — so a row it reads is at
  most `pipeline_depth` batch-writes stale;
* the rows whose writes are still "in flight" (folded into the live table
  but not yet in the snapshot) are filled with the PRES Eq. 7 prediction:
  the GMM trackers extrapolate the snapshot row over the staleness gap,
  exactly the mechanism the paper uses to bridge intra-batch temporal
  discontinuity. The memory-coherence term (Eq. 10) bounds the induced
  error the same way Sec. 4 bounds the discontinuity error.

Host-side, `EventStream.prefetch_batches` prepares batch i+1..i+K on a
background thread while batch i's fused memory-update/embed step runs, and
the epoch driver never syncs on per-step metrics (device scalars are
fetched once per epoch).

`pipeline_depth=0` is the strictly sequential schedule: `make_train_step`
and `run_epoch` delegate verbatim to `repro.train.loop`, so depth 0 is
bit-exact with the historical loop (pinned in tests/test_pipeline.py).
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import coherence, pres
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.graph.events import EventBatch
from repro.graph.negatives import sample_negatives
from repro.models import modules
from repro.models.mdgnn import MDGNNConfig, MemoryState
from repro.train import loop as loop_lib
from repro.utils import metrics as metrics_lib


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PipelineState:
    """Double-buffered read view of the memory table.

    `read_mem`/`read_last_update` are the snapshot the embedding stage
    reads; `pending` counts, per node, the event occurrences folded into
    the live table since the snapshot (the Eq. 7 "count" extrapolation
    scale for the staleness fill); `tick` counts steps since the last
    refresh (the snapshot is refreshed when tick + 1 >= pipeline_depth,
    bounding staleness by pipeline_depth batch-writes)."""
    read_mem: jnp.ndarray          # (N, D) — snapshot table
    read_last_update: jnp.ndarray  # (N,)   — snapshot last-update times
    pending: jnp.ndarray           # (N,)   — occurrences not yet visible
    tick: jnp.ndarray              # ()     — steps since last refresh

    @staticmethod
    def init(mem: MemoryState) -> "PipelineState":
        # genuine copies, not aliases: the train step donates BOTH the live
        # state and this snapshot, and XLA refuses to donate one buffer twice
        return PipelineState(
            read_mem=jnp.copy(mem.mem),
            read_last_update=jnp.copy(mem.last_update),
            pending=jnp.zeros(mem.mem.shape[:1], jnp.float32),
            tick=jnp.zeros((), jnp.int32),
        )


PIPELINE_STATE_AXES = PipelineState(
    read_mem=("nodes", "embed"), read_last_update=("nodes",),
    pending=("nodes",), tick=())


def stale_read_table(cfg: MDGNNConfig, pres_state, pstate: PipelineState,
                     live_last_update) -> jnp.ndarray:
    """The table the embedding stage reads: snapshot rows extrapolated over
    the staleness gap with PRES `predict` (Eq. 7).

    The extrapolation scale matches cfg.pres_scale: "count" uses the
    pending-occurrence count per node, "time" the gap between the live and
    snapshot last-update times. Nodes with no in-flight write have scale 0,
    so their rows pass through untouched; without PRES the trackers are
    empty (zero deltas) and this degrades to a raw stale read.

    With cfg.use_kernels the whole-table extrapolation runs in the
    registered Pallas kernel "pres_predict" — one elementwise pass over the
    (N, D) table instead of three (docs/KERNELS.md §pres_predict); the GMM
    mixture-mean gather stays in XLA."""
    n = pstate.read_mem.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    pres_ids = ids % cfg.pres_buckets if cfg.pres_buckets else ids
    if cfg.pres_scale == "count":
        scale = pstate.pending
    else:  # "time"
        scale = jnp.maximum(live_last_update - pstate.read_last_update, 0.0)
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        dmean = pres.mixture_mean(pres_state, pres_ids)
        from repro.train import routing
        filled = routing.replicated(cfg, functools.partial(
            kops.pres_predict, clip=cfg.pres_clip, mode=cfg.kernels_mode))(
            pstate.read_mem.astype(jnp.float32), dmean, scale)
    else:
        filled = pres.predict(pres_state, pstate.read_mem.astype(jnp.float32),
                              scale, pres_ids, clip=cfg.pres_clip)
    return filled.astype(pstate.read_mem.dtype)


def make_pipelined_train_step(cfg: MDGNNConfig, opt, gru_fn=None):
    """Jitted staleness-aware train step (requires cfg.pipeline_depth >= 1).

    Signature: (params, opt_state, state, pstate, prev_batch, pos, neg)
            -> (params, opt_state, state, pstate, metrics).

    Identical to loop.make_train_step except the embedding stage reads the
    PRES-filled snapshot (`stale_read_table`) instead of the just-written
    live table — the live write and the embed are thereby independent, so
    on a multi-stage deployment they overlap (docs/PIPELINE.md §Schedule).
    Gradient note: the BCE term reaches the message/GRU parameters only
    through the coherence/PRES path (the snapshot is constant w.r.t. this
    step's parameters) — the standard bounded-staleness trade."""
    if cfg.pipeline_depth < 1:
        raise ValueError("make_pipelined_train_step needs pipeline_depth >= 1"
                         " — depth 0 is loop.make_train_step")
    if cfg.scan_chunk > 1:
        from repro.train import scan as scan_lib
        scan_lib.check_schedule(cfg)  # raises: mutually exclusive schedules
    use_smooth = (cfg.use_smoothing if cfg.use_smoothing is not None
                  else cfg.use_pres)
    if not (use_smooth and cfg.beta):
        # The BCE reads only the constant snapshot, so the coherence term is
        # the ONLY path from the loss to the memory-module params (PRES
        # trackers are state, not params) — without it they would silently
        # stay frozen at init for the whole run.
        raise ValueError(
            "pipeline_depth >= 1 without the coherence-smoothing term would "
            "freeze the memory/message parameters (the embedding reads a "
            "snapshot that is constant w.r.t. them, so Eq. 10 is the only "
            "gradient path); set use_smoothing=True with beta > 0 (the "
            "default when use_pres=True), or train with pipeline_depth=0 "
            "(docs/PIPELINE.md §Staleness semantics)")
    if gru_fn is None:
        gru_fn = modules.kernel_memory_cell(cfg)

    def loss_and_state(params, state, pstate: PipelineState,
                       prev_batch: EventBatch, pos: EventBatch,
                       neg: EventBatch):
        # --------- MEMORY stage (live) — kernel routing in memory_and_pres
        with obs_trace.stage("memory_update"):
            mem2, info, fused, delta = loop_lib.memory_and_pres(
                params, cfg, state, prev_batch, gru_fn=gru_fn)
        state2 = dict(state, memory=mem2)
        # ------------------------------- staleness accounting + read view --
        # Sharded runs (cfg.n_shards > 1): the snapshot lives in NATURAL
        # layout — the shard exchange happens in the live MEMORY stage
        # above, while the embedding reads this replicated stale snapshot,
        # so the exchange and the embed overlap (docs/DISTRIBUTED.md
        # §Pipelined overlap). Only the refresh (every pipeline_depth
        # steps) gathers the live sharded table.
        if cfg.n_shards > 1:
            from repro.train import routing
            live_mem = routing.natural_memory(cfg, mem2)
            embed_base = routing.natural_state_view(cfg, state2)
            pres_nat = routing.natural_component_view(cfg, state["pres"],
                                                      "pres")
        else:
            live_mem, embed_base, pres_nat = mem2, state2, state["pres"]
        occ = jax.ops.segment_sum(
            info["mask"].astype(jnp.float32),
            jnp.where(info["mask"], info["nodes"], cfg.n_nodes),
            num_segments=cfg.n_nodes + 1)[:-1]
        pstate = dataclasses.replace(pstate, pending=pstate.pending + occ)
        read_tab = stale_read_table(cfg, pres_nat, pstate,
                                    live_mem.last_update)
        embed_state = dict(embed_base, memory=MemoryState(
            mem=read_tab, last_update=pstate.read_last_update))
        # --------------------------------------- EMBEDDING stage (stale) --
        with obs_trace.stage("embed"):
            logit_p, logit_n = loop_lib.endpoint_logits(params, cfg,
                                                        embed_state, pos, neg)
        with obs_trace.stage("loss"):
            loss = loop_lib.link_bce(logit_p, logit_n, pos.mask, neg.mask)
            pen = coherence.coherence_penalty(
                info["s_prev"], fused, mask=info["selected"] & info["mask"])
            # use_smooth/beta validated at builder scope: the coherence term
            # is the pipelined step's only gradient path to the memory params
            loss = loss + cfg.beta * pen
        # ------------------------------------------- snapshot refresh lag --
        refresh = (pstate.tick + 1) >= cfg.pipeline_depth
        pstate2 = PipelineState(
            read_mem=jnp.where(refresh, live_mem.mem, pstate.read_mem),
            read_last_update=jnp.where(refresh, live_mem.last_update,
                                       pstate.read_last_update),
            pending=jnp.where(refresh, 0.0, pstate.pending),
            tick=jnp.where(refresh, 0, pstate.tick + 1).astype(jnp.int32),
        )
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
            # staleness slot: batch-writes missing from the snapshot this
            # step's embed read (incl. the current in-flight write), in [1, K]
            aux["obs"] = loop_lib._obs_step_stats(
                params, cfg, info, fused, loss, pen, pos,
                staleness=(pstate.tick + 1).astype(jnp.float32))
            if "route_overflow_shards" in info:
                aux["route_overflow_shards"] = jax.lax.stop_gradient(
                    info["route_overflow_shards"])
        return loss, (state2, pstate2, aux)

    def train_step(params, opt_state, state, pstate, prev_batch, pos, neg):
        (loss, (state2, pstate2, aux)), grads = jax.value_and_grad(
            loss_and_state, has_aux=True)(params, state, pstate,
                                          prev_batch, pos, neg)
        with obs_trace.stage("apply"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                  params, updates)
        state2 = loop_lib.maintain_state(cfg, params, state2, aux, prev_batch)
        pstate2 = jax.lax.stop_gradient(pstate2)
        metrics = {"loss": loss, "coherence_penalty": aux["coherence_penalty"],
                   "logit_p": aux["logit_p"], "logit_n": aux["logit_n"],
                   # batch-writes missing from the snapshot THIS step's embed
                   # read (incl. the current in-flight write): in [1, K]
                   "staleness": pstate.tick + 1}
        if "route_overflow" in aux:
            metrics["route_overflow"] = aux["route_overflow"]
        for k in ("obs", "route_overflow_shards"):
            if k in aux:
                metrics[k] = aux[k]
        return params, opt_state, state2, pstate2, metrics

    # donate the carry buffers (opt state, model state, snapshot) so XLA
    # aliases the (N, D) tables in place — same contract as the sequential
    # and scanned steps (docs/SCAN.md §Donation)
    return loop_lib._replicating_inputs(
        cfg, jax.jit(train_step, donate_argnums=(1, 2, 3)), n_carry=4)


def make_train_step(cfg: MDGNNConfig, opt, gru_fn=None):
    """Facade: the sequential step at depth 0, the pipelined step otherwise."""
    if cfg.pipeline_depth == 0:
        return loop_lib.make_train_step(cfg, opt, gru_fn=gru_fn)
    return make_pipelined_train_step(cfg, opt, gru_fn=gru_fn)


def run_epoch(params, opt_state, state, batches, cfg: MDGNNConfig,
              train_step, key, dst_range, collect_logits=False):
    """Facade over loop.run_epoch: depth 0 delegates verbatim (bit-exact);
    depth >= 1 runs the pipelined schedule.

    `batches` may be a list OR a lazy/prefetching iterator
    (`EventStream.prefetch_batches`) — the pipelined driver consumes it
    pairwise, so host batch prep overlaps device compute. The PRNG key is
    split per step in the same order as loop.run_epoch, so negatives are
    identical across depths (the sweep compares schedules, not samples).
    Per-step metrics stay on device; the single host sync happens at epoch
    end (the sequential loop also defers its loss syncs to epoch end, but
    still pulls each step's logits — and the scan engine, repro.train.scan,
    amortizes even that to once per macro-batch)."""
    if cfg.pipeline_depth == 0:
        # loop.run_epoch consumes lists and lazy iterators alike
        return loop_lib.run_epoch(params, opt_state, state, batches, cfg,
                                  train_step, key, dst_range,
                                  collect_logits=collect_logits)
    t0 = time.perf_counter()
    if cfg.n_shards > 1:
        # the snapshot lives in natural layout (see make_pipelined_train_step)
        from repro.train import routing
        mem0 = jax.jit(lambda m: routing.natural_memory(cfg, m))(
            state["memory"])
        pstate = routing.replicate(PipelineState.init(mem0), cfg.n_shards)
    else:
        pstate = PipelineState.init(state["memory"])
    losses, pos_all, neg_all = [], [], []
    obs = obs_metrics.EpochObs()
    it = iter(batches)
    try:
        prev_batch = next(it)
        for batch in it:
            key, sub = jax.random.split(key)
            neg = sample_negatives(sub, batch, *dst_range)
            params, opt_state, state, pstate, m = train_step(
                params, opt_state, state, pstate, prev_batch, batch, neg)
            losses.append(m["loss"])
            pos_all.append(m["logit_p"])
            neg_all.append(m["logit_n"])
            obs.step(m)
            prev_batch = batch
    finally:
        # stop a PrefetchIterator's producer thread if the epoch aborts
        close = getattr(it, "close", None)
        if close is not None:
            close()
    # one host sync for the whole epoch
    losses = [float(x) for x in losses]
    pos_all = [np.asarray(x) for x in pos_all]
    neg_all = [np.asarray(x) for x in neg_all]
    route_overflow, obs_out = obs.finish()
    ap = metrics_lib.average_precision(np.concatenate(pos_all),
                                       np.concatenate(neg_all))
    aps = [metrics_lib.average_precision(p, n)
           for p, n in zip(pos_all, neg_all)] if collect_logits else []
    dt = time.perf_counter() - t0
    return params, opt_state, state, loop_lib.EpochResult(
        ap, float(np.mean(losses)), dt, aps,
        route_overflow=route_overflow, obs=obs_out)
