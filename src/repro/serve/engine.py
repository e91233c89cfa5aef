"""ServeEngine — device-resident MDGNN online inference (docs/SERVING.md).

The deployment regime PRES targets: a memory table that continuously folds
a live event stream while answering link/recommendation queries. The
engine keeps the full runtime state (memory table, neighbour ring buffers,
PRES GMM trackers, APAN mailbox) device-resident and exposes three jitted
entry points:

* `ingest(events)` — fold a micro-batch through the SAME fused
  memory-maintenance path training uses (`loop.memory_and_pres`, Pallas
  `memory_update` kernel under PRES+GRU) with donated state buffers, so
  the (N, D) table is updated in place. Late/out-of-order arrivals are
  folded, not dropped: PRES's predict-correct filter fuses each
  measurement with the GMM prediction exactly as it bridges intra-batch
  discontinuity at training time (§Late arrivals).
* `query(srcs, dsts, ts)` — link scores for candidate pairs, numerically
  identical to the offline `loop.evaluate` scoring (parity pinned to 1e-5
  in tests/test_serve.py).
* `recommend_topk(srcs, t, k)` — score every source against the full item
  memory through the fused `link_score` Pallas kernel and return the
  top-k items, entirely on device.

Requests are coalesced by a `MicroBatcher` into bucketed static shapes, so
the jit compile count is bounded by the bucket table (provable via
`trace_counts`); `warmup()` pre-compiles every bucket with masked no-op
batches so no live request pays a compile.
"""
from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as checkpoint_io
from repro.graph.events import EventBatch
from repro.models import mdgnn, modules
from repro.models.mdgnn import MDGNNConfig
from repro.obs import trace as obs_trace
from repro.serve.batcher import MicroBatcher
from repro.train import loop as loop_lib


class ServeEngine:
    """Online MDGNN inference over a device-resident memory state.

    `track_deltas=True` (the online default) keeps updating the PRES GMM
    trackers from serve-time deltas, so the predict-correct filter keeps
    learning the stream's drift; `track_deltas=False` freezes them, which
    makes ingest+query bit-compatible with the offline `loop.evaluate`
    pass (the parity contract tests/test_serve.py pins)."""

    def __init__(self, cfg: MDGNNConfig, params, state, *,
                 track_deltas: bool = True, batcher: MicroBatcher | None = None,
                 item_range: tuple[int, int] | None = None):
        self.cfg = cfg
        self.params = params
        self.state = state
        self.track_deltas = track_deltas
        self.batcher = batcher or MicroBatcher(d_edge=cfg.d_edge)
        self.item_range = item_range
        self.trace_counts: collections.Counter = collections.Counter()
        self._gru_fn = modules.kernel_memory_cell(cfg)
        # the ingest step donates the state buffers (the (N, D) table is
        # aliased in place, docs/SCAN.md §Donation) — callers must use the
        # rebound self.state only, which the host API below enforces
        self._ingest_fn = jax.jit(self._ingest_body, donate_argnums=(1,))
        self._query_fn = jax.jit(self._query_body)
        self._topk_fns: dict[int, object] = {}

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #

    @classmethod
    def from_checkpoint(cls, path: str, cfg: MDGNNConfig, *, shardings=None,
                        seed: int = 0, **kw) -> "ServeEngine":
        """Restore a training checkpoint ({"params", "state"} bundle, the
        launch/train.py --checkpoint format) into a live engine. `cfg` must
        match the training config (checkpoint/io.py verifies the tree
        structure and every leaf shape and raises a named error otherwise);
        `shardings` is an optional {"params": ..., "state": ...} tree
        forwarded to `load_checkpoint` so the restored tables land sharded
        (restore-onto-a-different-mesh, docs/SERVING.md §Checkpoint)."""
        params, _ = mdgnn.init_params(jax.random.PRNGKey(seed), cfg)
        like = {"params": params, "state": mdgnn.init_state(cfg)}
        bundle = checkpoint_io.load_checkpoint(path, like, shardings=shardings)
        return cls(cfg, bundle["params"], bundle["state"], **kw)

    # ------------------------------------------------------------------ #
    # jitted bodies (trace side effects count compiles per static shape)
    # ------------------------------------------------------------------ #

    def _ingest_body(self, params, state, batch: EventBatch):
        self.trace_counts[("ingest", batch.size)] += 1
        with obs_trace.stage("serve_ingest"):
            mem2, info, fused, delta = loop_lib.memory_and_pres(
                params, self.cfg, state, batch, gru_fn=self._gru_fn)
            state2 = dict(state, memory=mem2)
            aux = {"delta": delta, "info_nodes": info["nodes"],
                   "info_selected": info["selected"],
                   "info_mask": info["mask"]}
            # maintain_state updates neighbours + mailbox always, and the
            # PRES trackers iff cfg.use_pres — masking use_pres freezes the
            # trackers (the eval-parity mode) without touching the rest
            mcfg = (self.cfg if self.track_deltas
                    else dataclasses.replace(self.cfg, use_pres=False))
            return loop_lib.maintain_state(mcfg, params, state2, aux, batch)

    def _query_body(self, params, state, src, dst, t):
        self.trace_counts[("query", src.shape[0])] += 1
        with obs_trace.stage("serve_query"):
            b = src.shape[0]
            # one batched embedding call for both endpoint sets, exactly the
            # loop.endpoint_logits layout (per-node embeddings are
            # independent, so the coalesced call matches pairwise scoring
            # bit-for-bit)
            h = mdgnn.embed_nodes(params, self.cfg, state,
                                  jnp.concatenate([src, dst]),
                                  jnp.concatenate([t, t]))
            return mdgnn.link_logits(params, h[:b], h[b:])

    def _topk_body(self, params, state, src, t, *, k: int):
        self.trace_counts[("topk", src.shape[0], k)] += 1
        with obs_trace.stage("serve_topk"):
            lo, hi = self.item_range
            items = jnp.arange(lo, hi, dtype=jnp.int32)
            # item-side embeddings are shared across the coalesced query
            # batch, computed once at the batch's latest timestamp
            t_item = jnp.full((hi - lo,), jnp.max(t), jnp.float32)
            h = mdgnn.embed_nodes(params, self.cfg, state,
                                  jnp.concatenate([src, items]),
                                  jnp.concatenate([t, t_item]))
            h_src, h_items = h[:src.shape[0]], h[src.shape[0]:]
            dec = params["dec"]
            if self.cfg.use_kernels:
                from repro.kernels import ops as kops
                scores = kops.link_score(h_src, h_items, dec["w1"], dec["b1"],
                                         dec["w2"], dec["b2"],
                                         mode=self.cfg.kernels_mode)
            else:
                from repro.kernels import ref
                scores = ref.link_score_ref(h_src, h_items, dec["w1"],
                                            dec["b1"], dec["w2"], dec["b2"])
            vals, idx = jax.lax.top_k(scores, k)
            return vals, (idx + lo).astype(jnp.int32)

    def _get_topk_fn(self, k: int):
        fn = self._topk_fns.get(k)
        if fn is None:
            fn = jax.jit(functools.partial(self._topk_body, k=k))
            self._topk_fns[k] = fn
        return fn

    # ------------------------------------------------------------------ #
    # host API (micro-batched: pad-to-bucket, split-over-max)
    # ------------------------------------------------------------------ #

    # Host spans (obs.trace, recorded when enabled) tile each call: input
    # conversion, bucket padding and device puts (`serve.pad`), each jitted
    # call until it returns (`serve.dispatch`), and pulling answers back or
    # waiting on the state (`serve.pull`), each with the request's kind and
    # chunk index. The counters `serve.rows_valid` and `serve.rows_sent`
    # total the rows asked for and the rows sent after padding.

    @staticmethod
    def _count_rows(valid: int, sent: int) -> None:
        obs_trace.count("serve.rows_valid", valid)
        obs_trace.count("serve.rows_sent", sent)

    def ingest(self, src, dst, t, feat=None) -> int:
        """Fold a request of events (chronological *within* the request;
        late relative to already-folded events is fine) into the memory.
        Returns the number of events folded."""
        n = len(src)
        chunks = self.batcher.pad_events(src, dst, t, feat)
        for i, (lo, hi) in enumerate(self.batcher.chunk_spans(n)):
            with obs_trace.span("serve.pad", kind="ingest", chunk=i):
                eb = next(chunks)
                self._count_rows(hi - lo, eb.size)
            with obs_trace.span("serve.dispatch", kind="ingest", chunk=i):
                self.state = self._ingest_fn(self.params, self.state, eb)
        return n

    def ingest_batch(self, batch: EventBatch) -> None:
        """Fold an already-padded EventBatch (e.g. a temporal batch from an
        offline replay) without re-bucketing — adds that batch's size to
        the compile-shape set, so live traffic should use `ingest`."""
        with obs_trace.span("serve.dispatch", kind="ingest", chunk=0):
            self.state = self._ingest_fn(self.params, self.state, batch)

    def query(self, src, dst, t) -> np.ndarray:
        """Link scores for candidate (src, dst) pairs at query times `t`."""
        with obs_trace.span("serve.pad", kind="query"):
            src = np.asarray(src, np.int32)
            dst = np.asarray(dst, np.int32)
            t = np.asarray(t, np.float32)
        n = len(src)
        if n == 0:
            return np.zeros((0,), np.float32)
        out = []
        for i, (lo, hi) in enumerate(self.batcher.chunk_spans(n)):
            with obs_trace.span("serve.pad", kind="query", chunk=i):
                s, d, tt, valid = self.batcher.pad_query(
                    src[lo:hi], dst[lo:hi], t[lo:hi])
                self._count_rows(valid, s.shape[0])
            with obs_trace.span("serve.dispatch", kind="query", chunk=i):
                scores = self._query_fn(self.params, self.state, s, d, tt)
            with obs_trace.span("serve.pull", kind="query", chunk=i):
                out.append(np.asarray(scores)[:valid])
        return np.concatenate(out)

    def recommend_topk(self, src, t, k: int):
        """Top-k candidate items per source, scored against the FULL item
        memory on device. Returns (scores (B, k), item_ids (B, k))."""
        if self.item_range is None:
            raise ValueError("recommend_topk needs the engine constructed "
                             "with item_range=(item_lo, item_hi)")
        n_items = self.item_range[1] - self.item_range[0]
        if not 0 < k <= n_items:
            raise ValueError(f"k must be in [1, {n_items}], got {k}")
        with obs_trace.span("serve.pad", kind="topk"):
            src = np.asarray(src, np.int32)
            t = np.asarray(t, np.float32)
            fn = self._get_topk_fn(k)
        vals_out, ids_out = [], []
        for i, (lo, hi) in enumerate(self.batcher.chunk_spans(len(src))):
            with obs_trace.span("serve.pad", kind="topk", chunk=i):
                s, _, tt, valid = self.batcher.pad_query(
                    src[lo:hi], np.zeros(hi - lo, np.int32), t[lo:hi])
                self._count_rows(valid, s.shape[0])
            with obs_trace.span("serve.dispatch", kind="topk", chunk=i):
                vals, ids = fn(self.params, self.state, s, tt)
            with obs_trace.span("serve.pull", kind="topk", chunk=i):
                vals_out.append(np.asarray(vals)[:valid])
                ids_out.append(np.asarray(ids)[:valid])
        return np.concatenate(vals_out), np.concatenate(ids_out)

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #

    def warmup(self, *, query: bool = True, topk_k: int | None = None) -> None:
        """Pre-compile every bucket so no live request pays a compile.

        Ingest warm-up uses fully-masked no-op batches: every write in the
        fold path is mask-gated (drop-slot scatters, masked ring appends,
        masked tracker segment sums), so folding an all-padding batch is a
        numeric no-op — the executable gets built, the state stays
        bit-identical (pinned in tests/test_serve.py)."""
        if topk_k is not None and self.item_range is None:
            raise ValueError("warmup(topk_k=...) needs the engine "
                             "constructed with item_range=(item_lo, item_hi)")
        d_edge = self.batcher.d_edge
        for b in self.batcher.buckets:
            eb = EventBatch(
                src=jnp.zeros((b,), jnp.int32),
                dst=jnp.zeros((b,), jnp.int32),
                t=jnp.zeros((b,), jnp.float32),
                feat=jnp.zeros((b, d_edge), jnp.float32),
                mask=jnp.zeros((b,), bool))
            self.state = self._ingest_fn(self.params, self.state, eb)
            if query:
                z = jnp.zeros((b,), jnp.int32)
                self._query_fn(self.params, self.state, z, z,
                               jnp.zeros((b,), jnp.float32))
            if topk_k is not None:
                self._get_topk_fn(topk_k)(self.params, self.state,
                                          jnp.zeros((b,), jnp.int32),
                                          jnp.zeros((b,), jnp.float32))
        self.block_until_ready()

    def block_until_ready(self) -> None:
        with obs_trace.span("serve.pull", kind="state"):
            jax.block_until_ready(self.state)
