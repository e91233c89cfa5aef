"""Kernel registry + backend-aware execution policy for the Pallas kernels.

Every kernel is registered as a `KernelSpec`: the differentiable Pallas
entry point (custom_vjp forward, oracle backward), the pure-jnp oracle in
`repro.kernels.ref` it must match bit-for-bit in interpret mode (the parity
target the tests and the CI kernel-parity step check), and the default
block-size policy. `dispatch(name, ...)` is the single entry point the
model/training code routes through; the legacy per-kernel functions below
remain as thin dispatch aliases.

Execution policy (docs/KERNELS.md §Execution policy): dispatch picks, per
kernel x shape x backend, one of three modes —

    compiled   Pallas lowered by Mosaic (interpret=False; TPU)
    interpret  Pallas body executed op-by-op (same numerics; any backend)
    oracle     the jitted pure-jnp ref — XLA's fusion of the same math

resolved with precedence: per-call `mode=` kwarg (an explicit `interpret=`
kwarg counts as one) > `REPRO_KERNELS_MODE` env var > the persisted
autotune cache (repro.kernels.autotune, keyed by backend + kernel + shape
signature) > the backend default (tpu -> compiled, anything else ->
oracle). The CPU default is the oracle because interpret mode executes the
kernel body in Python — measurably slower than XLA at every shape this
model emits (results/bench/fig_scan.json before/after) — while the oracle
IS the reference computation, so `use_kernels` stays a no-loss switch.
Backend/env resolution is cached once per process; `execution_policy()`
exposes the resolved policy for logs and bench metadata.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
from typing import Any, Callable, Mapping

import jax

from repro.kernels import embed_attn as _ea
from repro.kernels import flash_attn as _fa
from repro.kernels import gru_cell as _gru
from repro.kernels import link_score as _ls
from repro.kernels import memory_update as _mu
from repro.kernels import neighbor_attn as _nattn
from repro.kernels import pres_filter as _pf
from repro.kernels import ref
from repro.kernels import ssd_chunk as _ssd

MODES = ("auto", "compiled", "interpret", "oracle")
ENV_VAR = "REPRO_KERNELS_MODE"


def _check_mode(mode: str) -> None:
    if mode not in MODES:
        raise ValueError(f"unknown kernel execution mode {mode!r}; valid "
                         f"modes: {', '.join(MODES)} (per-call mode=, "
                         f"cfg.kernels_mode, or the {ENV_VAR} env var)")


@functools.lru_cache(maxsize=None)
def backend() -> str:
    """jax.default_backend(), resolved once per process (it walks the
    device client on every call — measurable at dispatch rates)."""
    return jax.default_backend()


@functools.lru_cache(maxsize=None)
def _env_mode() -> str | None:
    """REPRO_KERNELS_MODE, validated and cached. Unset/"auto" -> None
    (fall through to the autotune cache, then the backend default)."""
    raw = os.environ.get(ENV_VAR, "").strip().lower()
    if not raw or raw == "auto":
        return None
    _check_mode(raw)
    return raw


def _backend_default() -> str:
    return "compiled" if backend() == "tpu" else "oracle"


def reset_execution_policy() -> None:
    """Drop every per-process policy memo (backend, env mode, autotune
    file, jitted oracles) — for tests that flip the env var or swap the
    autotune cache mid-process."""
    from repro.kernels import autotune
    backend.cache_clear()
    _env_mode.cache_clear()
    _oracle_fn.cache_clear()
    autotune.clear_cache()


def execution_policy() -> dict:
    """The resolved execution policy, for logs and bench metadata."""
    from repro.kernels import autotune
    return {
        "backend": backend(),
        "env_mode": _env_mode(),
        "default_mode": _env_mode() or _backend_default(),
        "autotune_entries": autotune.n_entries(backend()),
        "autotune_cache": str(autotune.cache_path(backend())),
    }


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered Pallas kernel and its validation contract."""
    name: str
    impl: Callable[..., Any]       # differentiable Pallas entry point
    ref: Callable[..., Any]        # pure-jnp oracle (parity + VJP target)
    blocks: Mapping[str, int]      # default tile sizes forwarded to impl
    doc: str                       # one-line role (details: docs/KERNELS.md)
    # oracle-mode adapter when the ref's calling convention differs from
    # the impl's (e.g. ssd_chunk_ref is per-sample; the impl is batched)
    oracle: Callable[..., Any] | None = None
    # kwargs only the Pallas impl understands (stripped, with the block
    # sizes and `interpret`, before the oracle is called)
    impl_only: tuple[str, ...] = ()
    # for a kernel with several forms chosen by a static kwarg: the form's
    # name from the call's kwargs — the name its pallas_call carries in a
    # profiler trace, and its key in the dispatch log
    form_name: Callable[[Mapping[str, Any]], str] | None = None


REGISTRY: dict[str, KernelSpec] = {}


def _register(spec: KernelSpec) -> None:
    REGISTRY[spec.name] = spec


def _ssd_chunk_oracle(q, k, v, lcum, h0):
    return jax.vmap(ref.ssd_chunk_ref)(q, k, v, lcum, h0)


_register(KernelSpec(
    name="gru_cell", impl=_gru.gru_cell, ref=ref.gru_cell_ref,
    blocks={"block_m": 128},
    doc="fused GRU memory cell (both matmuls + gates, one HBM round trip)"))
_register(KernelSpec(
    name="pres_filter", impl=_pf.pres_filter, ref=ref.pres_filter_ref,
    blocks={"block_m": 256},
    doc="PRES predict->correct->delta-rate over touched rows (Eqs. 7-9)"))
_register(KernelSpec(
    name="pres_predict", impl=_mu.pres_predict, ref=ref.pres_predict_ref,
    blocks={"block_m": 256},
    doc="standalone Eq. 7 extrapolation (pipeline staleness fill)"))
_register(KernelSpec(
    name="memory_update", impl=_mu.memory_update, ref=ref.memory_update_ref,
    blocks={"block_m": 128},
    doc="fused GRU + PRES filter + delta-rate memory-maintenance step"))
_register(KernelSpec(
    name="memory_update_table",
    impl=_mu.memory_update_table, ref=ref.memory_update_table_ref,
    blocks={"block_m": 32},
    doc="touched-row gather + fused GRU or RNN cell + PRES update + table "
        "scatter-back in ONE pass (aliased (N, D) table, docs/KERNELS.md)",
    form_name=lambda kw: _mu.table_kernel_name(kw.get("cell", "gru"))))
_register(KernelSpec(
    name="link_score", impl=_ls.link_score, ref=ref.link_score_ref,
    blocks={"block_b": 32, "block_i": 128},
    doc="pairwise link-decoder scores (serve recommend-topk, VMEM hidden)"))
_register(KernelSpec(
    name="neighbor_attn", impl=_nattn.neighbor_attn,
    ref=ref.neighbor_attn_ref, blocks={"block_m": 128},
    doc="TGN temporal neighbour attention (softmax stays in VMEM)"))
_register(KernelSpec(
    name="embed_attn", impl=_ea.embed_attn, ref=ref.embed_attn_ref,
    blocks={"block_r": 16},
    doc="dedup-frontier embedding layer: unique-table gather + time-encode "
        "+ QKV + masked softmax in one pass (docs/KERNELS.md §embed_attn)"))
_register(KernelSpec(
    name="ssd_chunk", impl=_ssd.ssd_chunk, ref=ref.ssd_chunk_ref,
    blocks={}, oracle=_ssd_chunk_oracle,
    doc="one SSD / mLSTM chunk with carried state"))
_register(KernelSpec(
    name="flash_attn", impl=_fa.flash_attn, ref=_fa.flash_attn_ref,
    blocks={}, impl_only=("q_block", "kv_block"),
    doc="flash attention (causal/windowed/GQA) for the zoo substrate"))


def get_kernel(name: str) -> KernelSpec:
    """Look up a registered kernel (raises KeyError with the known names)."""
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: "
                       f"{sorted(REGISTRY)}") from None


@functools.lru_cache(maxsize=None)
def _oracle_fn(name: str, kw_items: tuple) -> Callable:
    """One jitted oracle per (kernel, static kwargs). The refs are pure
    jnp, so jit gives XLA's fused executable of the exact parity target —
    differentiable without a custom VJP."""
    spec = REGISTRY[name]
    fn = spec.oracle or spec.ref
    return jax.jit(functools.partial(fn, **dict(kw_items)))


# Kernel-dispatch log (docs/OBSERVABILITY.md §Kernel-dispatch table):
# (kernel form, resolved mode) -> dispatch-call count; a kernel's form is
# its registry name unless the spec names its forms (`form_name`).
# dispatch() runs at TRACE
# time — once per jit compilation, not per executed step — so the log is a
# per-process record of which execution-policy branch each kernel actually
# took, at zero steady-state cost. The obs sink stamps it into every
# run-log epilogue.
DISPATCH_LOG: collections.Counter = collections.Counter()


def dispatch_log() -> dict:
    """{kernel: {mode: dispatch_count}} since process start / last reset."""
    out: dict = {}
    for (name, mode), cnt in sorted(DISPATCH_LOG.items()):
        out.setdefault(name, {})[mode] = cnt
    return out


def reset_dispatch_log() -> None:
    DISPATCH_LOG.clear()


def dispatch(name: str, *args, mode: str | None = None, **kw):
    """Single dispatch point: resolve the execution mode (per-call >
    env > autotune cache > backend default), merge block sizes (per-call >
    autotune cache > registry default), then run the Pallas impl or the
    jitted oracle."""
    spec = get_kernel(name)
    if mode is not None and mode != "auto":
        _check_mode(mode)
    elif "interpret" in kw:
        # an explicit interpret= kwarg is a per-call Pallas-mode override
        # (the historical API every kernel test uses) — like mode=, it
        # beats the env var and the autotune cache
        mode = "interpret" if kw["interpret"] else "compiled"
    else:
        mode = _env_mode()
    sel_blocks: Mapping[str, int] = {}
    if mode is None:
        from repro.kernels import autotune
        sel = autotune.lookup(backend(), name, args)
        if sel is not None:
            mode = sel.get("mode")
            sel_blocks = sel.get("blocks", {})
    if mode is None or mode == "auto":
        mode = _backend_default()
    for k, v in {**dict(spec.blocks), **dict(sel_blocks)}.items():
        kw.setdefault(k, v)
    DISPATCH_LOG[(spec.form_name(kw) if spec.form_name else name,
                  mode)] += 1
    if mode == "oracle":
        strip = set(spec.blocks) | set(spec.impl_only) | {"interpret"}
        okw = tuple(sorted((k, v) for k, v in kw.items() if k not in strip))
        return _oracle_fn(name, okw)(*args)
    kw.setdefault("interpret", mode == "interpret")
    return spec.impl(*args, **kw)


# ---------------------------------------------------------------------------
# Legacy per-kernel wrappers (thin dispatch aliases)
# ---------------------------------------------------------------------------


def gru_cell(x, h, w, u, b, **kw):
    return dispatch("gru_cell", x, h, w, u, b, **kw)


def gru_cell_params(params, x, h, **kw):
    """Adapter matching repro.models.modules.gru_cell(params, x, h)."""
    return gru_cell(x, h, params["w"], params["u"], params["b"], **kw)


def pres_filter(s_prev, s_meas, delta_mean, dt, gamma, **kw):
    return dispatch("pres_filter", s_prev, s_meas, delta_mean, dt, gamma, **kw)


def pres_predict(s_prev, delta_mean, scale, **kw):
    return dispatch("pres_predict", s_prev, delta_mean, scale, **kw)


def memory_update(x, h, w, u, b, delta_mean, scale, gamma, **kw):
    return dispatch("memory_update", x, h, w, u, b, delta_mean, scale, gamma,
                    **kw)


def memory_update_table(table, last_t, x, gather_idx, write_idx, times,
                        w, u, b, delta_mean, scale, gamma, **kw):
    """Fused touched-row pass: gather h from `table` at gather_idx, run the
    memory cell (`cell=` "gru" or "rnn") and the PRES filter, scatter the
    fused rows back at write_idx (row n_nodes = masked-write dump,
    n_nodes+1 = masked-read zeros source). Returns (new_table, new_last_t,
    s_meas, fused, delta)."""
    return dispatch("memory_update_table", table, last_t, x, gather_idx,
                    write_idx, times, w, u, b, delta_mean, scale, gamma, **kw)


def link_score(h_src, h_items, w1, b1, w2, b2, **kw):
    return dispatch("link_score", h_src, h_items, w1, b1, w2, b2, **kw)


def neighbor_attn(q, k, v, valid, **kw):
    return dispatch("neighbor_attn", q, k, v, valid, **kw)


def embed_attn(h_self, tab, idx, dt, valid, tw, tb, wq, wk, wv, **kw):
    """Fused dedup-frontier embedding layer: gather each row's K neighbour
    hidden rows from the unique table at idx, time-encode, project Q/K/V,
    masked multi-head softmax — one pass (docs/KERNELS.md §embed_attn)."""
    return dispatch("embed_attn", h_self, tab, idx, dt, valid, tw, tb,
                    wq, wk, wv, **kw)


def ssd_chunk(q, k, v, lcum, h0, **kw):
    return dispatch("ssd_chunk", q, k, v, lcum, h0, **kw)


def flash_attn(q, k, v, **kw):
    return dispatch("flash_attn", q, k, v, **kw)
