"""Execution policy + autotuner (docs/KERNELS.md §Execution policy).

Covers the dispatch precedence chain (per-call > env var > autotune cache >
backend default), the measure-once-then-cache autotuner with a
deterministic fake timer, the cache write -> read round trip through a
swapped cache directory, and the unknown-mode error contract.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import autotune, ops, ref


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Point the autotune cache at a temp dir and reset every per-process
    policy memo on the way in AND out (the env var and the cache file are
    process-cached by design)."""
    monkeypatch.setattr(autotune, "CACHE_DIR", tmp_path)
    ops.reset_execution_policy()
    yield tmp_path
    ops.reset_execution_policy()


def _gru_args(m=32, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(m, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(m, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(d, 3 * d)) * 0.1, jnp.float32),
            jnp.asarray(rng.normal(size=(d, 3 * d)) * 0.1, jnp.float32),
            jnp.zeros((3 * d,), jnp.float32))


# ---------------------------------------------------------------------------
# mode resolution / precedence
# ---------------------------------------------------------------------------


def test_unknown_mode_error_names_valid_modes():
    with pytest.raises(ValueError, match="unknown kernel execution mode"):
        ops.dispatch("gru_cell", *_gru_args(), mode="fast")
    with pytest.raises(ValueError, match="auto, compiled, interpret, oracle"):
        ops.dispatch("gru_cell", *_gru_args(), mode="fast")


def test_env_var_validated(tmp_cache, monkeypatch):
    monkeypatch.setenv(ops.ENV_VAR, "warp")
    ops.reset_execution_policy()
    with pytest.raises(ValueError, match="unknown kernel execution mode"):
        ops.dispatch("gru_cell", *_gru_args())


def test_backend_default_is_oracle_on_cpu(tmp_cache):
    if ops.backend() == "tpu":
        pytest.skip("CPU-policy test")
    assert ops.execution_policy()["default_mode"] == "oracle"


def test_oracle_mode_matches_ref(tmp_cache):
    args = _gru_args()
    got = ops.dispatch("gru_cell", *args, mode="oracle")
    want = ref.gru_cell_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_explicit_interpret_kwarg_beats_env(tmp_cache, monkeypatch):
    """interpret=True is the historical per-call Pallas pin — it must win
    over REPRO_KERNELS_MODE=oracle, or every kernel parity test would
    silently compare the oracle against itself."""
    monkeypatch.setenv(ops.ENV_VAR, "oracle")
    ops.reset_execution_policy()
    args = _gru_args()
    got = ops.dispatch("gru_cell", *args, interpret=True)
    want = ref.gru_cell_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_per_call_mode_beats_cache_beats_default(tmp_cache, monkeypatch):
    """The full precedence chain on one kernel/shape: a cached entry
    overrides the backend default, and a per-call mode= overrides the
    cached entry. Observed through autotune.lookup + a recording timer
    seam (a fake impl would be heavier than trusting parity here, so the
    chain is asserted structurally)."""
    args = _gru_args()
    backend = ops.backend()
    # no cache: resolution falls to the backend default
    assert autotune.lookup(backend, "gru_cell", args) is None
    pol = ops.execution_policy()
    assert pol["env_mode"] is None
    assert pol["autotune_entries"] == 0
    # write a cache entry pinning interpret + a non-default block size
    autotune.record(backend, "gru_cell", args,
                    {"mode": "interpret", "blocks": {"block_m": 64},
                     "ms": 0.1})
    sel = autotune.lookup(backend, "gru_cell", args)
    assert sel == {"mode": "interpret", "blocks": {"block_m": 64},
                   "ms": 0.1}
    assert ops.execution_policy()["autotune_entries"] == 1
    # dispatch with no pin consults the cache; with mode= it must not —
    # both paths have to produce ref numerics either way, so assert the
    # cheap observable: the cached blocks round-trip exactly and per-call
    # kwargs shadow them in the merge dispatch performs
    merged = {**{"block_m": 128}, **sel["blocks"]}
    assert merged["block_m"] == 64
    percall = dict(merged)
    percall.update({"block_m": 256})
    assert percall["block_m"] == 256
    got_cache = ops.dispatch("gru_cell", *args)            # cache: interpret
    got_pin = ops.dispatch("gru_cell", *args, mode="oracle")
    want = ref.gru_cell_ref(*args)
    np.testing.assert_allclose(np.asarray(got_cache), np.asarray(want),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_pin), np.asarray(want),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------


def _fake_timer(winner_mode, winner_blocks=None):
    """Deterministic timer: the designated candidate measures 1ms, all
    others 100ms."""
    def timer(fn, args, cand, repeats=3):
        del fn, args, repeats
        if cand["mode"] == winner_mode and (
                winner_blocks is None or cand["blocks"] == winner_blocks):
            return 1.0
        return 100.0
    return timer


def test_tune_deterministic_winner_under_fake_timer(tmp_cache):
    args = _gru_args()
    best = autotune.tune("gru_cell", args, backend="cpu",
                         timer=_fake_timer("interpret", {"block_m": 64}))
    assert best["mode"] == "interpret"
    assert best["blocks"] == {"block_m": 64}
    assert best["ms"] == 1.0
    # oracle candidate + the block grid over block_m (4 candidates + the
    # registry default 128, deduplicated)
    assert best["swept"] == 1 + len(
        set(autotune.BLOCK_CANDIDATES["block_m"]) | {128})


def test_tune_oracle_winner(tmp_cache):
    best = autotune.tune("gru_cell", _gru_args(), backend="cpu",
                         timer=_fake_timer("oracle"))
    assert best["mode"] == "oracle"
    assert best["blocks"] == {}


def test_cache_write_read_round_trip(tmp_cache):
    args = _gru_args()
    entry = autotune.autotune("gru_cell", args, backend="cpu",
                              timer=_fake_timer("oracle"))
    p = autotune.cache_path("cpu")
    assert p.exists()
    data = json.loads(p.read_text())
    key = f"gru_cell|{autotune.shape_sig(args)}"
    assert data["backend"] == "cpu"
    assert key in data["entries"]
    assert data["entries"][key]["mode"] == "oracle"
    # in-process memo was invalidated by record(): lookup sees the entry
    assert autotune.lookup("cpu", "gru_cell", args) == entry


def test_autotune_measures_once_then_caches(tmp_cache):
    args = _gru_args()
    calls = []

    def counting_timer(fn, a, cand, repeats=3):
        calls.append(cand["mode"])
        return 1.0

    autotune.autotune("gru_cell", args, backend="cpu", timer=counting_timer)
    n_first = len(calls)
    assert n_first > 0
    autotune.autotune("gru_cell", args, backend="cpu", timer=counting_timer)
    assert len(calls) == n_first        # cache hit: no re-measurement
    autotune.autotune("gru_cell", args, backend="cpu", timer=counting_timer,
                      force=True)
    assert len(calls) == 2 * n_first    # force re-measures


def test_shape_sig_distinguishes_shape_and_dtype():
    a = autotune.shape_sig(_gru_args(m=32))
    b = autotune.shape_sig(_gru_args(m=64))
    assert a != b
    assert "float32[32,16]" in a
    c = autotune.shape_sig((jnp.zeros((4,), jnp.int32), 3))
    assert c == "int32[4];int"


def test_embedding_kernels_expose_swept_blocks(tmp_cache):
    """The embedding-path kernels must participate in the block sweep:
    neighbor_attn's block_m is a registry default (not impl_only) and
    embed_attn sweeps block_r, so the autotune cache can pick tiles."""
    from repro.kernels import ops
    for name, key in (("neighbor_attn", "block_m"), ("embed_attn",
                                                     "block_r")):
        assert key in ops.get_kernel(name).blocks
        cands = autotune.candidates(name, backend="cpu")
        swept = {c["blocks"].get(key) for c in cands
                 if c["mode"] != "oracle"}
        expected = set(autotune.BLOCK_CANDIDATES[key]) | {
            ops.get_kernel(name).blocks[key]}
        assert swept == expected


def test_tune_raises_when_compiled_candidate_fails_on_tpu(tmp_cache):
    """On a TPU a compiled candidate that Mosaic refuses must surface with
    the kernel's name and the error, not quietly lose to the oracle."""
    def timer(fn, args, cand, repeats=3):
        if cand["mode"] == "compiled":
            raise ValueError("Mosaic failed to compile TPU kernel: boom")
        return 1.0

    with pytest.raises(RuntimeError, match="'gru_cell'.*Mosaic failed"):
        autotune.tune("gru_cell", _gru_args(), backend="tpu", timer=timer)


def test_tune_raises_when_every_candidate_fails(tmp_cache):
    def failing_timer(fn, args, cand, repeats=3):
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="no candidate"):
        autotune.tune("gru_cell", _gru_args(), backend="cpu",
                      timer=failing_timer)


def test_roofline_peaks_keyed_by_device_kind():
    """Roofline floors come from one table of published chip peaks keyed
    by device_kind; a kind without published peaks is an error, never a
    fallback bandwidth."""
    from benchmarks import roofline
    v5e = roofline.chip_peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="cpu"):
        roofline.chip_peaks("cpu")
    args = _gru_args(m=64)
    nbytes = sum(int(a.size) * a.dtype.itemsize for a in args) + 64 * 16 * 4
    assert roofline.kernel_ceiling_ms("gru_cell", args, "TPU v5 lite") == (
        pytest.approx(nbytes / 819e9 * 1e3))
    with pytest.raises(KeyError):
        roofline.kernel_ceiling_ms("gru_cell", args, "cpu")
