"""Telemetry-layer contracts (docs/OBSERVABILITY.md):

* obs vector pack/unpack round-trip and schema-drift rejection;
* the zero-sync contract — with telemetry on, the jitted step traces
  exactly as often as with telemetry off, and the whole epoch performs
  exactly ONE additional host fetch regardless of the step count;
* EpochObs accumulation for per-step ((F,)) and scan-stacked ((T, F))
  payloads, including the per-shard overflow totals;
* fixed log-spaced latency histograms + upper-edge percentile estimates,
  and their integration into the serve replay report;
* the JSONL sink: manifest-first round-trip through read_runlog, loud
  rejection of malformed files, and canonical() log equality for two
  runs of the same seeded computation;
* tools/inspect_run.py rendering a run-log into the report sections the
  acceptance criteria name;
* host spans and counters: free when disabled, a `repro.`-prefixed
  profiler annotation when enabled, tiling each training step and each
  serve call; the `maintain_state` stage scope in the compiled step.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.graph.negatives import sample_negatives
from repro.models import mdgnn, modules
from repro.models.mdgnn import MDGNNConfig
from repro.obs import metrics as obs_metrics
from repro.obs import sink
from repro.obs import trace as obs_trace
from repro.optim import optimizers
from repro.serve import MicroBatcher, ServeEngine, replay
from repro.train import loop, pipeline, scan


def _cfg(stream, **kw):
    base = dict(variant="tgn", n_nodes=stream.num_nodes,
                d_edge=stream.feat_dim, d_mem=16, d_msg=16, d_time=8,
                d_embed=16, n_neighbors=4, use_pres=True, obs_metrics=True)
    base.update(kw)
    return MDGNNConfig(**base)


def _init(cfg, seed=0):
    params, _ = mdgnn.init_params(jax.random.PRNGKey(seed), cfg)
    opt = optimizers.adamw(1e-3)
    return params, opt, opt.init(params), mdgnn.init_state(cfg)


# ---------------------------------------------------------------------------
# obs vector schema
# ---------------------------------------------------------------------------


def test_pack_unpack_roundtrip():
    vec = obs_metrics.pack_train_obs(loss=0.5, coherence_cos=0.9,
                                     pres_delta_mean=0.1, events=64.0)
    assert vec.shape == (len(obs_metrics.TRAIN_OBS_FIELDS),)
    series = obs_metrics.unpack_series(np.asarray(vec))
    assert series["loss"] == [0.5]
    assert series["coherence_cos"] == [pytest.approx(0.9)]
    assert series["pres_delta_mean"] == [pytest.approx(0.1)]
    assert series["events"] == [64.0]
    assert series["staleness"] == [0.0]          # unnamed fields default 0


def test_pack_rejects_unknown_field():
    # schema drift must be explicit: extend TRAIN_OBS_FIELDS, never pass
    # ad-hoc names that would silently vanish
    with pytest.raises(KeyError, match="unknown obs field"):
        obs_metrics.pack_train_obs(losss=0.5)


def test_pres_delta_stats_masked():
    s_pred = jnp.zeros((4, 3))
    s_meas = jnp.array([[3.0, 4.0, 0.0],     # norm 5, written
                        [1.0, 0.0, 0.0],     # norm 1, masked OUT
                        [0.0, 0.0, 2.0],     # norm 2, written
                        [9.0, 9.0, 9.0]])    # masked OUT
    written = jnp.array([True, False, True, False])
    mean, mx, cnt = obs_metrics.pres_delta_stats(s_pred, s_meas, written)
    assert float(cnt) == 2.0
    assert float(mean) == pytest.approx(3.5)
    assert float(mx) == pytest.approx(5.0)
    # all-masked steps: zeros, not NaN
    mean, mx, cnt = obs_metrics.pres_delta_stats(
        s_pred, s_meas, jnp.zeros(4, bool))
    assert float(mean) == float(mx) == float(cnt) == 0.0


# ---------------------------------------------------------------------------
# latency histograms
# ---------------------------------------------------------------------------


def test_log_bucket_edges_validation():
    edges = obs_metrics.log_bucket_edges(1.0, 100.0, 2)
    np.testing.assert_allclose(edges, [1.0, 10.0, 100.0])
    for lo, hi, n in ((0.0, 1.0, 4), (1.0, 1.0, 4), (1.0, 2.0, 0)):
        with pytest.raises(ValueError):
            obs_metrics.log_bucket_edges(lo, hi, n)


def test_latency_hist_clamps_and_counts():
    edges = obs_metrics.log_bucket_edges(1.0, 1000.0, 3)   # 1/10/100/1000 ms
    h = obs_metrics.latency_hist(
        [0.0000001, 0.005, 0.05, 0.5, 99.0], edges_ms=edges)
    # under/overflow clamp into the end buckets; counts always sum to n
    assert h["counts"] == [2, 1, 2]
    assert h["n"] == 5 == sum(h["counts"])
    assert h["edges_ms"] == [float(e) for e in edges]


def test_hist_percentile_upper_edge():
    edges = obs_metrics.log_bucket_edges(1.0, 1000.0, 3)
    h = obs_metrics.latency_hist([0.002] * 98 + [0.5] * 2, edges_ms=edges)
    assert obs_metrics.hist_percentile(h, 50) == pytest.approx(10.0)
    assert obs_metrics.hist_percentile(h, 99) == pytest.approx(1000.0)
    assert obs_metrics.hist_percentile(
        {"edges_ms": list(edges), "counts": [0, 0, 0]}, 99) == 0.0


def test_replay_reports_full_histograms(tiny_stream, tiny_spec):
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    cfg = _cfg(tiny_stream, obs_metrics=False)
    params, _, _, state = _init(cfg)
    eng = ServeEngine(cfg, params, state, item_range=dst,
                      batcher=MicroBatcher(buckets=(16, 64),
                                           d_edge=tiny_stream.feat_dim))
    rep = replay(eng, tiny_stream, dst, rate=20000.0, tick=0.004,
                 query_batch=8, max_events=200, seed=0)
    for hist in (rep.ingest_hist, rep.query_hist):
        assert hist["n"] == rep.n_ticks == sum(hist["counts"])
        assert len(hist["counts"]) == len(hist["edges_ms"]) - 1
    # the point estimates the report prints stay consistent with the
    # histogram's conservative upper-edge estimates
    assert rep.ingest_p50_ms <= obs_metrics.hist_percentile(
        rep.ingest_hist, 50)


# ---------------------------------------------------------------------------
# EpochObs accumulation
# ---------------------------------------------------------------------------


def test_epoch_obs_empty():
    assert obs_metrics.EpochObs().finish() == (0, None)


def test_epoch_obs_per_step_vectors():
    eo = obs_metrics.EpochObs()
    for i in range(3):
        m = {"obs": obs_metrics.pack_train_obs(loss=float(i), events=10.0),
             "route_overflow": jnp.asarray(i),
             "route_overflow_shards": jnp.asarray([i, 2 * i])}
        eo.step(m)
        # telemetry payloads are POPPED (engines must not double-handle
        # them); route_overflow stays for the engines' own bookkeeping
        assert "obs" not in m and "route_overflow_shards" not in m
        assert "route_overflow" in m
    total, out = eo.finish()
    assert total == 3
    assert out["steps"] == 3
    assert out["series"]["loss"] == [0.0, 1.0, 2.0]
    assert out["series"]["events"] == [10.0] * 3
    assert out["route_overflow_shards"] == [3, 6]


def test_epoch_obs_scan_stacked_chunks():
    # the scan engine emits (T, F) stacks per macro-step; a ragged tail
    # chunk must concatenate cleanly with the full ones
    eo = obs_metrics.EpochObs()
    for t, base in ((3, 0.0), (2, 3.0)):
        rows = jnp.stack([obs_metrics.pack_train_obs(loss=base + i)
                          for i in range(t)])
        eo.step({"obs": rows, "route_overflow": jnp.ones(t, jnp.int32),
                 "route_overflow_shards": jnp.ones((t, 2), jnp.int32)})
    total, out = eo.finish()
    assert total == 5
    assert out["steps"] == 5
    assert out["series"]["loss"] == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert out["route_overflow_shards"] == [5, 5]


# ---------------------------------------------------------------------------
# the zero-sync contract
# ---------------------------------------------------------------------------


def _run_epoch_counting(stream, spec, obs_on: bool):
    """One sequential epoch with a spy memory cell; returns (trace calls,
    host-fetch delta, EpochResult)."""
    calls = []

    def spy_cell(params, x, h):
        calls.append(1)
        return modules.gru_cell(params, x, h)

    cfg = _cfg(stream, obs_metrics=obs_on)
    params, opt, opt_state, state = _init(cfg)
    step = loop.make_train_step(cfg, opt, gru_fn=spy_cell)
    dst = (spec.n_users, spec.n_users + spec.n_items)
    before = obs_metrics.host_fetches()
    *_, res = loop.run_epoch(params, opt_state, state,
                             stream.temporal_batches(100), cfg, step,
                             jax.random.PRNGKey(0), dst)
    return len(calls), obs_metrics.host_fetches() - before, res


def test_zero_sync_contract(tiny_stream, tiny_spec):
    """Telemetry must be free where it matters: same jit trace count as
    metrics-off, and exactly one extra host fetch for the WHOLE epoch
    (the batched EpochObs flush), independent of the number of steps."""
    traces_off, fetches_off, res_off = _run_epoch_counting(
        tiny_stream, tiny_spec, obs_on=False)
    traces_on, fetches_on, res_on = _run_epoch_counting(
        tiny_stream, tiny_spec, obs_on=True)
    assert traces_on == traces_off          # no retraces from telemetry
    assert fetches_off == 0
    assert fetches_on == 1                  # one flush per epoch, not per step
    assert res_off.obs is None
    n_steps = tiny_stream.num_batches(100) - 1
    assert res_on.obs["steps"] == n_steps
    for field in obs_metrics.TRAIN_OBS_FIELDS:
        assert len(res_on.obs["series"][field]) == n_steps
    # observing must not change what is observed
    assert res_on.loss == pytest.approx(res_off.loss, abs=1e-6)
    # and the series must agree with the epoch's own loss aggregate
    assert np.mean(res_on.obs["series"]["loss"]) == pytest.approx(
        res_on.loss, abs=1e-5)
    assert max(res_on.obs["series"]["staleness"]) == 0.0   # sequential
    assert res_on.obs["series"]["pres_delta_events"][-1] > 0


def test_scan_engine_obs_matches_sequential(tiny_stream, tiny_spec):
    """The scan-compiled engine's stacked telemetry must unpack to the
    same per-step series the sequential loop records."""
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    batches = tiny_stream.temporal_batches(100)
    series = {}
    for chunk in (1, 2):
        cfg = _cfg(tiny_stream, scan_chunk=chunk)
        params, opt, opt_state, state = _init(cfg)
        if chunk == 1:
            step = loop.make_train_step(cfg, opt)
            *_, res = loop.run_epoch(params, opt_state, state, batches, cfg,
                                     step, jax.random.PRNGKey(3), dst)
        else:
            eng = scan.ScanEngine(cfg, opt)
            *_, res = eng.run_epoch(params, opt_state, state, batches,
                                    jax.random.PRNGKey(3), dst)
        series[chunk] = res.obs["series"]
    assert series[1].keys() == series[2].keys()
    np.testing.assert_allclose(series[1]["loss"], series[2]["loss"],
                               atol=1e-5)
    np.testing.assert_allclose(series[1]["pres_delta_mean"],
                               series[2]["pres_delta_mean"], atol=1e-4)


def test_pipelined_staleness_series(tiny_stream, tiny_spec):
    """Depth-K pipelined training reports its real snapshot staleness
    (1..K ticks) through the obs series."""
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    cfg = _cfg(tiny_stream, pipeline_depth=2)
    params, opt, opt_state, state = _init(cfg)
    step = pipeline.make_train_step(cfg, opt)
    *_, res = pipeline.run_epoch(params, opt_state, state,
                                 tiny_stream.temporal_batches(100), cfg,
                                 step, jax.random.PRNGKey(0), dst)
    stale = res.obs["series"]["staleness"]
    assert min(stale) >= 1.0 and max(stale) <= cfg.pipeline_depth
    assert max(stale) == cfg.pipeline_depth     # the cycle reaches depth K


def test_gmm_health_probe(tiny_stream, tiny_spec):
    dst = (tiny_spec.n_users, tiny_spec.n_users + tiny_spec.n_items)
    cfg = _cfg(tiny_stream)
    params, opt, opt_state, state = _init(cfg)
    step = loop.make_train_step(cfg, opt)
    *_, state, _ = loop.run_epoch(params, opt_state, state,
                                  tiny_stream.temporal_batches(100), cfg,
                                  step, jax.random.PRNGKey(0), dst)
    h = obs_metrics.gmm_health(state["pres"])
    assert set(h) == {"tracked_fraction", "observations", "mean_abs_mu",
                      "mean_var", "max_var"}
    assert 0.0 < h["tracked_fraction"] <= 1.0
    assert h["observations"] > 0
    assert h["max_var"] >= h["mean_var"] >= 0.0


# ---------------------------------------------------------------------------
# sink: JSONL round-trip, rejection, canonical equality
# ---------------------------------------------------------------------------


def test_runlog_roundtrip(tmp_path, tiny_stream):
    path = tmp_path / "run.jsonl"
    cfg = _cfg(tiny_stream)
    with sink.RunLog(path, role="train", cfg=cfg, argv=["--x"]) as log:
        log.write("epoch", epoch=0, loss=np.float32(0.5),
                  series={"loss": np.asarray([0.5, 0.4])})
    records = sink.read_runlog(path)
    man = records[0]
    assert man["schema_version"] == sink.SCHEMA_VERSION
    assert man["role"] == "train"
    assert man["argv"] == ["--x"]
    assert man["obs_fields"] == list(obs_metrics.TRAIN_OBS_FIELDS)
    assert man["meta"]["cfg_digest"] == sink.cfg_digest(cfg)
    assert man["cfg"]["obs_metrics"] is True
    ep = [r for r in records if r["kind"] == "epoch"]
    assert ep[0]["loss"] == 0.5                      # numpy coerced to JSON
    assert ep[0]["series"]["loss"] == [0.5, pytest.approx(0.4)]
    assert records[-1]["kind"] == "end"
    with pytest.raises(ValueError, match="closed"):
        log.write("epoch", epoch=1)


def test_read_runlog_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text("not json at all\n")
    with pytest.raises(ValueError, match="not JSONL"):
        sink.read_runlog(bad)
    no_manifest = tmp_path / "nm.jsonl"
    no_manifest.write_text(json.dumps({"kind": "epoch"}) + "\n")
    with pytest.raises(ValueError, match="manifest"):
        sink.read_runlog(no_manifest)
    future = tmp_path / "future.jsonl"
    future.write_text(json.dumps({"kind": "manifest",
                                  "schema_version": 999}) + "\n")
    with pytest.raises(ValueError, match="schema_version"):
        sink.read_runlog(future)


def test_canonical_strips_wall_clock():
    records = [
        {"kind": "manifest", "schema_version": 1, "t_start": 123.0,
         "meta": {"git_commit": "abc", "cpu_count": 8}},
        {"kind": "epoch", "loss": 0.5, "seconds": 9.9,
         "series": {"loss": [0.5]}, "events_per_sec": 1e4},
        {"kind": "spans", "summary": {}},
        {"kind": "end", "t_end": 456.0},
    ]
    canon = sink.canonical(records)
    assert [r["kind"] for r in canon] == ["manifest", "epoch"]
    assert "t_start" not in canon[0]
    assert "seconds" not in canon[1] and "events_per_sec" not in canon[1]
    assert canon[1]["series"] == {"loss": [0.5]}     # data survives


def test_cfg_digest_tracks_config(tiny_stream):
    a = _cfg(tiny_stream)
    b = _cfg(tiny_stream)
    c = _cfg(tiny_stream, d_mem=32, d_msg=32, d_embed=32)
    assert sink.cfg_digest(a) == sink.cfg_digest(b)
    assert sink.cfg_digest(a) != sink.cfg_digest(c)


def _write_seeded_runlog(path, stream, spec):
    dst = (spec.n_users, spec.n_users + spec.n_items)
    cfg = _cfg(stream)
    params, opt, opt_state, state = _init(cfg)
    step = loop.make_train_step(cfg, opt)
    *_, res = loop.run_epoch(params, opt_state, state,
                             stream.temporal_batches(100), cfg, step,
                             jax.random.PRNGKey(7), dst)
    with sink.RunLog(path, role="train", cfg=cfg, argv=[]) as log:
        log.write("epoch", epoch=0, loss=res.loss, seconds=res.seconds,
                  route_overflow=res.route_overflow,
                  steps=res.obs["steps"], series=res.obs["series"])


def test_deterministic_runs_produce_equal_canonical_logs(tmp_path,
                                                         tiny_stream,
                                                         tiny_spec):
    """Two runs of the same seeded epoch must write run-logs that compare
    EQUAL after canonical() strips the wall clock — the telemetry series
    is a pure function of (seed, data, config)."""
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_seeded_runlog(a, tiny_stream, tiny_spec)
    _write_seeded_runlog(b, tiny_stream, tiny_spec)
    assert sink.canonical(sink.read_runlog(a)) == \
        sink.canonical(sink.read_runlog(b))


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


def test_spans_disabled_by_default_and_drain():
    obs_trace.drain()
    with obs_trace.span("noop_stage"):
        pass
    assert obs_trace.drain() == []          # no-op unless enabled
    obs_trace.enable()
    try:
        with obs_trace.span("real_stage"):
            pass
        spans = obs_trace.drain()
    finally:
        obs_trace.disable()
    assert [s["name"] for s in spans] == ["real_stage"]
    assert spans[0]["dur_s"] >= 0.0
    summ = obs_trace.span_summary(spans)
    assert summ["real_stage"]["count"] == 1
    assert obs_trace.drain() == []          # drained means drained


class _Annotations:
    """Stands in for jax.profiler.TraceAnnotation and records its uses."""

    def __init__(self):
        self.opened = []

    def __call__(self, name, **args):
        self.opened.append((name, args))
        return contextlib.nullcontext()


def test_disabled_span_reads_no_clock_and_opens_no_annotation(monkeypatch):
    import time
    reads, ann = [], _Annotations()
    real = time.perf_counter
    monkeypatch.setattr(time, "perf_counter",
                        lambda: reads.append(1) or real())
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    assert not obs_trace.enabled()
    first = obs_trace.span("train.sample", step=0)
    with first:
        with obs_trace.span("serve.pad", kind="query", chunk=1):
            obs_trace.count("serve.rows_valid", 5)
    # one shared no-op object: no generator, no timer, no annotation
    assert obs_trace.span("other") is first
    assert reads == [] and ann.opened == []
    assert obs_trace.drain() == [] and obs_trace.drain_counts() == {}


def test_enabled_span_annotates_with_prefix_and_args_and_counts(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    obs_trace.enable()
    try:
        with obs_trace.span("train.dispatch", step=3):
            pass
        with obs_trace.span("prefetch_wait"):
            pass
        obs_trace.count("serve.rows_valid", 690)
        obs_trace.count("serve.rows_sent", 1024)
        obs_trace.count("serve.rows_valid", 10)
        spans, counts = obs_trace.drain(), obs_trace.drain_counts()
    finally:
        obs_trace.disable()
    assert ann.opened == [("repro.train.dispatch", {"step": 3}),
                          ("repro.prefetch_wait", {})]
    assert [(s["name"], s.get("args")) for s in spans] == [
        ("train.dispatch", {"step": 3}), ("prefetch_wait", None)]
    assert counts == {"serve.rows_valid": 700, "serve.rows_sent": 1024}
    assert obs_trace.drain_counts() == {}     # drained means drained


def test_runlog_writes_counts_beside_spans(tmp_path):
    obs_trace.enable()
    try:
        log = sink.RunLog(tmp_path / "r.jsonl", role="serve")
        with obs_trace.span("serve.pull", kind="state"):
            pass
        obs_trace.count("serve.rows_sent", 16)
        log.close()
    finally:
        obs_trace.disable()
    recs = {r["kind"]: r for r in sink.read_runlog(tmp_path / "r.jsonl")}
    assert recs["counts"]["counts"] == {"serve.rows_sent": 16}
    assert recs["spans"]["spans"][0]["args"] == {"kind": "state"}


def _epoch_spans(stream, spec, runner):
    dst = (spec.n_users, spec.n_users + spec.n_items)
    depth = 1 if runner == "pipelined" else 0
    cfg = _cfg(stream, pipeline_depth=depth, obs_metrics=False)
    params, opt, opt_state, state = _init(cfg)
    batches = stream.temporal_batches(100)
    obs_trace.enable()
    try:
        if runner == "evaluate":
            loop.evaluate(params, state, batches, cfg,
                          loop.make_eval_step(cfg), jax.random.PRNGKey(0),
                          dst)
        else:
            pipeline.run_epoch(params, opt_state, state, batches, cfg,
                               pipeline.make_train_step(cfg, opt),
                               jax.random.PRNGKey(0), dst)
        return obs_trace.drain(), len(batches) - 1
    finally:
        obs_trace.disable()


@pytest.mark.parametrize("runner", ["sequential", "pipelined", "evaluate"])
def test_epoch_spans_tile_each_step(tiny_stream, tiny_spec, runner):
    """Each step of an epoch is one sample, one dispatch and one pull span,
    in that order and carrying the step index; the epoch ends in one
    epoch_end span."""
    spans, n_steps = _epoch_spans(tiny_stream, tiny_spec, runner)
    names = [s["name"] for s in spans]
    assert names == ["train.sample", "train.dispatch",
                     "train.pull"] * n_steps + ["train.epoch_end"]
    assert [s["args"]["step"] for s in spans[:-1]] == [
        i for i in range(n_steps) for _ in range(3)]
    ends = [s["t0"] + s["dur_s"] for s in spans]
    assert all(b["t0"] >= a for a, b in zip(ends, spans[1:]))


def test_train_step_hlo_scopes_maintain_state(tiny_stream):
    """The state maintenance after the optimizer update runs under its own
    stage scope, so its device ops carry the name in their op_name."""
    cfg = _cfg(tiny_stream, obs_metrics=False)
    params, opt, opt_state, state = _init(cfg)
    b = tiny_stream.temporal_batches(100)
    neg = sample_negatives(jax.random.PRNGKey(0), b[1], 50, 80)
    text = loop.make_train_step(cfg, opt).lower(
        params, opt_state, state, b[0], b[1], neg).compile().as_text()
    ops = re.findall(r'op_name="([^"]*)"', text)
    scoped = [o for o in ops if "/maintain_state/" in o]
    assert scoped and not any("maintain_state" in o and "embed" in o
                              for o in ops)


def test_engine_counts_valid_and_sent_rows(tiny_stream, tiny_spec):
    """A query of 1,380 pairs goes out as two chunks of the 1,024 bucket:
    1,380 rows asked for, 2,048 sent, one dispatch span per chunk."""
    cfg = _cfg(tiny_stream, obs_metrics=False)
    params, _, _, state = _init(cfg)
    eng = ServeEngine(cfg, params, state,
                      batcher=MicroBatcher(d_edge=tiny_stream.feat_dim))
    rng = np.random.default_rng(0)
    n = 1380
    src = rng.integers(0, tiny_spec.n_users, n)
    dst = rng.integers(tiny_spec.n_users,
                       tiny_spec.n_users + tiny_spec.n_items, n)
    obs_trace.enable()
    try:
        scores = eng.query(src, dst, np.full(n, 1.0))
        spans, counts = obs_trace.drain(), obs_trace.drain_counts()
    finally:
        obs_trace.disable()
    assert scores.shape == (n,)
    assert counts == {"serve.rows_valid": 1380, "serve.rows_sent": 2048}
    dispatch = [s["args"] for s in spans if s["name"] == "serve.dispatch"]
    assert dispatch == [
        {"kind": "query", "chunk": 0}, {"kind": "query", "chunk": 1}]
    assert [s["name"] for s in spans] == (
        ["serve.pad"] + ["serve.pad", "serve.dispatch", "serve.pull"] * 2)


# ---------------------------------------------------------------------------
# inspector
# ---------------------------------------------------------------------------


def _load_inspector():
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "inspect_run", root / "tools" / "inspect_run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_inspector_renders_acceptance_sections(tmp_path, tiny_stream,
                                               tiny_spec):
    """The report must contain the sections the acceptance criteria name:
    PRES prediction-error percentiles, staleness/overflow counters, the
    kernel-dispatch table, and the serve latency histograms."""
    inspect_run = _load_inspector()
    path = tmp_path / "run.jsonl"
    cfg = _cfg(tiny_stream, pipeline_depth=2)
    n = len(obs_metrics.TRAIN_OBS_FIELDS)
    rows = np.zeros((4, n))
    series = obs_metrics.unpack_series(rows)
    series.update(pres_delta_mean=[0.5, 0.6, 0.7, 0.8],
                  pres_delta_max=[1.0, 2.0, 1.5, 1.2],
                  pres_delta_events=[10.0] * 4,
                  coherence_cos=[0.1, 0.8, 0.9, 0.95],
                  staleness=[1.0, 2.0, 1.0, 2.0])
    with sink.RunLog(path, role="train", cfg=cfg, argv=[]) as log:
        log.write("epoch", epoch=0, loss=0.5, seconds=2.0,
                  events_per_sec=1000.0, route_overflow=7, steps=4,
                  series=series, route_overflow_shards=[3, 4],
                  gmm_health={"tracked_fraction": 0.5, "observations": 10,
                              "mean_abs_mu": 0.1, "mean_var": 0.01,
                              "max_var": 0.2})
        log.write("serve", n_events=100, n_queries=50, n_ticks=5,
                  events_per_sec=1e4, queries_per_sec=5e3, online_ap=0.5,
                  ingest_hist=obs_metrics.latency_hist([0.001, 0.002]),
                  query_hist=obs_metrics.latency_hist([0.003]),
                  post_warmup_traces={"ingest 16": 2})
        log.write("kernel_dispatch",
                  table={"memory_update_table": {"oracle": 3}})
    report = inspect_run.render(sink.read_runlog(path))
    for needle in ("PRES prediction error", "p99", "staleness",
                   "Route overflow", "shard  1", "GMM tracker health",
                   "Kernel dispatch", "memory_update_table",
                   "Ingest latency", "ingest 16",
                   "Memory-coherence cosine"):
        assert needle in report, f"report missing {needle!r}"


def test_inspector_cli_exit_codes(tmp_path, capsys):
    inspect_run = _load_inspector()
    bad = tmp_path / "bad.jsonl"
    bad.write_text("garbage\n")
    assert inspect_run.main([str(bad)]) == 1
    with sink.RunLog(tmp_path / "ok.jsonl", role="train", argv=[]):
        pass
    assert inspect_run.main([str(tmp_path / "ok.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "Run report" in out
