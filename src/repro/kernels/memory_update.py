"""Fused memory-maintenance Pallas kernels (the full per-batch update path).

`memory_update` fuses the three stages the sequential loop runs per temporal
batch over the touched memory rows — GRU gates (measurement), PRES Eq. 7
predict + Eq. 8 correct, and the Eq. 9 delta-rate statistic — into ONE pass:
a row tile is read from HBM once, both GRU matmuls hit the MXU while the
gates, the extrapolation and the fusion stay resident in VMEM, and the tile
is written back once as (s_meas, fused, delta). Unfused this is ~10 HBM
round trips per row (6 for the GRU, 4 for the filter); fused it is one read
+ one write — the TGL/MSPipe observation that batched-MDGNN throughput is
won in exactly this scatter/update primitive.

`memory_update_table` is the table-level form the training step actually
dispatches: the same fused math with the memory-row gather and the
write-back scatter pulled INTO the kernel. The (N, D) table stays in HBM
(`memory_space=pl.ANY`, aliased in place); each grid step DMAs its
`block_m` gathered rows into a VMEM tile and DMAs the fused rows back
(docs/KERNELS.md §memory_update_table — including the occurrence-order
precondition that makes the in-place scatter hazard-free).

`pres_predict` is the standalone Eq. 7 extrapolation used by the pipelined
schedule's staleness fill (`train/pipeline.py::stale_read_table`): one
elementwise pass over the whole table instead of three.

The GMM mixture-mean gather stays OUTSIDE all of these (that gather mixes
tracker state across components — `core/pres.py::mixture_mean`); the
kernels take the gathered δ̄ rows. Per-row scalars travel as (M, 1)
lane-shaped blocks and the Eq. 8 gate as an SMEM scalar, the layouts
Mosaic accepts. Shapes/tiling, the execution policy and the registry
dispatch are documented in docs/KERNELS.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling


def _gru_pres(x, h, w, u, b, dmean, scale, gamma, *, clip, delta_mode):
    """The fused GRU + PRES math on one VMEM tile (values, not refs):
    x (bm, Din), h/dmean (bm, D), scale (bm, 1), b (1, 3D), gamma scalar.
    Returns (s_meas, fused, delta)."""
    # ---- GRU gates: both matmuls back-to-back on the MXU ------------------
    gx = jnp.dot(x, w, preferred_element_type=jnp.float32) + b
    gh = jnp.dot(h, u, preferred_element_type=jnp.float32)
    d = h.shape[-1]
    rx, zx, nx = gx[:, :d], gx[:, d:2 * d], gx[:, 2 * d:]
    rh, zh, nh = gh[:, :d], gh[:, d:2 * d], gh[:, 2 * d:]
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    s_meas = (1.0 - z) * h + z * n
    # ---- PRES predict (Eq. 7) -> correct (Eq. 8) -> delta rate (Eq. 9) ----
    s_pred = h + jnp.clip(scale * dmean, -clip, clip)
    fused = (1.0 - gamma) * s_pred + gamma * s_meas
    base = s_pred if delta_mode == "innovation" else h
    delta = (fused - base) / jnp.maximum(scale, 1.0)
    return s_meas, fused, delta


def _memory_update_kernel(x_ref, h_ref, w_ref, u_ref, b_ref, dmean_ref,
                          scale_ref, gamma_ref, meas_ref, fused_ref,
                          delta_ref, *, clip, delta_mode):
    s_meas, fused, delta = _gru_pres(
        x_ref[...].astype(jnp.float32), h_ref[...].astype(jnp.float32),
        w_ref[...], u_ref[...], b_ref[...],
        dmean_ref[...].astype(jnp.float32), scale_ref[...], gamma_ref[0, 0],
        clip=clip, delta_mode=delta_mode)
    meas_ref[...] = s_meas.astype(meas_ref.dtype)
    fused_ref[...] = fused.astype(fused_ref.dtype)
    delta_ref[...] = delta.astype(delta_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "clip", "delta_mode",
                                             "interpret"))
def _memory_update_pallas(x, h, w, u, b, delta_mean, scale, gamma, *,
                          block_m: int = 128, clip: float = 5.0,
                          delta_mode: str = "innovation",
                          interpret: bool = True):
    """x: (M, Din) messages, h: (M, D) previous rows, w: (Din, 3D),
    u: (D, 3D), b: (3D,), delta_mean: (M, D) gathered GMM mixture means,
    scale: (M,) Eq. 7 extrapolation scale, gamma: scalar Eq. 8 gate.
    Returns (s_meas, fused, delta), each (M, D) fp32."""
    m, din = x.shape
    d = h.shape[-1]
    x, h, delta_mean, scale = tiling.pad_rows(
        block_m, x, h, delta_mean, scale.astype(jnp.float32)[:, None])
    mm = x.shape[0]
    row = lambda i: (i, 0)
    whole = lambda i: (0, 0)
    meas, fused, delta = pl.pallas_call(
        functools.partial(_memory_update_kernel, clip=clip,
                          delta_mode=delta_mode),
        grid=(mm // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, din), row),
            pl.BlockSpec((block_m, d), row),
            pl.BlockSpec((din, 3 * d), whole),
            pl.BlockSpec((d, 3 * d), whole),
            pl.BlockSpec((1, 3 * d), whole),
            pl.BlockSpec((block_m, d), row),
            pl.BlockSpec((block_m, 1), row),
            tiling.SMEM_SCALAR,
        ],
        out_specs=[pl.BlockSpec((block_m, d), row)] * 3,
        out_shape=[tiling.out_struct((mm, d), jnp.float32, x, h,
                                     delta_mean, scale)] * 3,
        interpret=interpret,
        name="memory_update",
    )(x, h, w, u, b.reshape(1, 3 * d), delta_mean, scale,
      jnp.reshape(gamma.astype(jnp.float32), (1, 1)))
    return meas[:m], fused[:m], delta[:m]


@functools.lru_cache(maxsize=None)
def _diff_memory_update(block_m: int, clip: float, delta_mode: str,
                        interpret: bool):
    """Pallas forward, oracle backward (kernels/autodiff.py::oracle_vjp).
    Gradients flow to the GRU weights, the messages/rows and gamma;
    delta_mean/scale come from PRES tracker STATE, so their cotangents are
    computed but discarded by the step's value_and_grad over params."""
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_memory_update_pallas, block_m=block_m, clip=clip,
                          delta_mode=delta_mode, interpret=interpret),
        functools.partial(ref.memory_update_ref, clip=clip,
                          delta_mode=delta_mode))


def memory_update(x, h, w, u, b, delta_mean, scale, gamma, *,
                  block_m: int = 128, clip: float = 5.0,
                  delta_mode: str = "innovation", interpret: bool = True):
    """Differentiable fused memory-maintenance step (GRU + PRES filter +
    delta-rate) — see module docstring and docs/KERNELS.md."""
    return _diff_memory_update(block_m, clip, delta_mode, interpret)(
        x, h, w, u, b, delta_mean, scale, gamma)


# ---------------------------------------------------------------------------
# Fused touched-row table pass: gather -> memory_update -> scatter-back
# ---------------------------------------------------------------------------


def _memory_update_table_kernel(g_ref, wi_ref, tab_in, x_ref, ok_ref, w_ref,
                                u_ref, b_ref, dmean_ref, scale_ref,
                                gamma_ref, tab_out, meas_ref, fused_ref,
                                delta_ref, h_buf, f_buf, sems, *, n, d,
                                block_m, clip, delta_mode):
    del tab_in  # the same HBM buffer as tab_out (input_output_aliases)
    c = h_buf.shape[0]
    base = pl.program_id(0) * block_m

    def gather(j, k):
        # masked occurrences (index n + 1) read a clamped real row; ok_ref
        # zeroes them below, so the table needs no padding rows
        row = jnp.minimum(g_ref[base + j], n - 1)
        return tiling.slab_copy(tab_out, row, h_buf, (j,), sems.at[0], c, k)

    def scatter(j, k):
        return tiling.slab_copy(tab_out, wi_ref[base + j], f_buf, (j,),
                                sems.at[1], c, k, to_table=True)

    copies = [(j, k) for j in range(block_m) for k in range(c)]
    for j, k in copies:
        gather(j, k).start()
    for j, k in copies:
        gather(j, k).wait()
    h = jnp.where(ok_ref[...] > 0, tiling.from_slabs(h_buf[...], d), 0.0)
    s_meas, fused, delta = _gru_pres(
        x_ref[...].astype(jnp.float32), h, w_ref[...], u_ref[...],
        b_ref[...], dmean_ref[...].astype(jnp.float32), scale_ref[...],
        gamma_ref[0, 0], clip=clip, delta_mode=delta_mode)
    for k, slab in enumerate(tiling.to_slabs(fused, c)):
        f_buf[k] = slab
    meas_ref[...] = s_meas.astype(meas_ref.dtype)
    fused_ref[...] = fused.astype(fused_ref.dtype)
    delta_ref[...] = delta.astype(delta_ref.dtype)
    # write-back: only selected occurrences (write index < n) are stored;
    # the drop slot n is skipped instead of written to a padded dump row
    for j, k in copies:
        @pl.when(wi_ref[base + j] < n)
        def _():
            scatter(j, k).start()
    for j, k in copies:
        @pl.when(wi_ref[base + j] < n)
        def _():
            scatter(j, k).wait()


@functools.partial(jax.jit, static_argnames=("block_m", "clip", "delta_mode",
                                             "interpret"))
def _memory_update_table_pallas(table, last_t, x, gather_idx, write_idx,
                                times, w, u, b, delta_mean, scale, gamma, *,
                                block_m: int = 32, clip: float = 5.0,
                                delta_mode: str = "innovation",
                                interpret: bool = True):
    """table: (N, D) memory, last_t: (N,), x: (M, Din) messages,
    gather_idx/write_idx: (M,) int32 row indices (N = masked-write drop
    slot, N + 1 = all-zeros masked read), times: (M,); weights/PRES args
    as in memory_update. Returns (new_table, new_last_t, s_meas, fused,
    delta).

    One pass over the M occurrences in tiles of `block_m`: each grid step
    DMAs its tile's rows from the HBM-resident (aliased) table into VMEM,
    runs the fused GRU+PRES math, and DMAs the selected fused rows back,
    updating the table in place (through the 128-lane slab view of
    kernels/tiling.py). The per-occurrence
    timestamps scatter into last_t outside the kernel (one scalar per row;
    XLA's scatter).

    CORRECTNESS PRECONDITION (hazard-freedom through the aliased table):
    occurrences must be ordered so that every gather of a node's row
    happens at a position <= that node's written (selected) position, and
    masked occurrences must gather row N + 1. mdgnn.occurrence_order
    produces exactly this order. A tile gathers all of its rows before it
    writes any, so a gather in the same tile as the write still reads the
    old row; a write of tile T never targets a row that a later tile
    gathers, so prefetching tile T + 1's gathers ahead of tile T's writes
    would stay hazard-free too. The oracle gathers everything up front, so
    any violation shows up as a parity failure, not silent corruption."""
    n, d = table.shape
    m, din = x.shape
    c = tiling.n_slabs(d)
    gidx, widx, x, dmean, sc = tiling.pad_rows(
        block_m, gather_idx.astype(jnp.int32), write_idx.astype(jnp.int32),
        x, delta_mean, scale.astype(jnp.float32)[:, None])
    if gidx.shape[0] > m:   # padded occurrences: masked read, dropped write
        gidx = gidx.at[m:].set(n + 1)
        widx = widx.at[m:].set(n)
    ok = (gidx < n).astype(jnp.float32)[:, None]
    mm = gidx.shape[0]
    like = (table, x, gidx, widx, dmean, sc)
    row = lambda i, g, wi: (i, 0)
    whole = lambda i, g, wi: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mm // block_m,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),           # table (HBM, alias)
            pl.BlockSpec((block_m, din), row),           # x
            pl.BlockSpec((block_m, 1), row),             # gather-valid
            pl.BlockSpec((din, 3 * d), whole),           # w
            pl.BlockSpec((d, 3 * d), whole),             # u
            pl.BlockSpec((1, 3 * d), whole),             # b
            pl.BlockSpec((block_m, d), row),             # dmean
            pl.BlockSpec((block_m, 1), row),             # scale
            tiling.SMEM_SCALAR,                          # gamma
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),           # table (HBM, alias)
            pl.BlockSpec((block_m, d), row),             # s_meas
            pl.BlockSpec((block_m, d), row),             # fused
            pl.BlockSpec((block_m, d), row),             # delta
        ],
        scratch_shapes=[
            pltpu.VMEM((c, block_m, tiling.LANES), jnp.float32),  # gathered
            pltpu.VMEM((c, block_m, tiling.LANES), jnp.float32),  # to write
            pltpu.SemaphoreType.DMA((2,)),               # gather, scatter
        ])
    outs = pl.pallas_call(
        functools.partial(_memory_update_table_kernel, n=n, d=d,
                          block_m=block_m, clip=clip, delta_mode=delta_mode),
        grid_spec=grid_spec,
        out_shape=[
            tiling.out_struct((n * c, tiling.LANES), jnp.float32, *like),
            *[tiling.out_struct((mm, d), jnp.float32, *like)] * 3,
        ],
        # operand indices count the two prefetched scalar arrays first:
        # 2 = table -> output 0 (in-place table)
        input_output_aliases={2: 0},
        interpret=interpret,
        name="memory_update_table",
    )(gidx, widx, tiling.slab_view(table), x, ok, w, u, b.reshape(1, 3 * d),
      dmean, sc, jnp.reshape(gamma.astype(jnp.float32), (1, 1)))
    new_tab = tiling.from_slab_view(outs[0], n, d, table.dtype)
    new_lt = last_t.at[write_idx].set(times.astype(last_t.dtype),
                                      mode="drop")
    return new_tab, new_lt, outs[1][:m], outs[2][:m], outs[3][:m]


@functools.lru_cache(maxsize=None)
def _diff_memory_update_table(block_m: int, clip: float, delta_mode: str,
                              interpret: bool):
    """Pallas forward, oracle backward. The int32 index args get float0
    cotangents from jax.vjp of the ref (same convention as neighbor_attn's
    bool mask); the table cotangent flows through the oracle's
    gather/scatter transposes."""
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_memory_update_table_pallas, block_m=block_m,
                          clip=clip, delta_mode=delta_mode,
                          interpret=interpret),
        functools.partial(ref.memory_update_table_ref, clip=clip,
                          delta_mode=delta_mode))


def memory_update_table(table, last_t, x, gather_idx, write_idx, times,
                        w, u, b, delta_mean, scale, gamma, *,
                        block_m: int = 32, clip: float = 5.0,
                        delta_mode: str = "innovation",
                        interpret: bool = True):
    """Differentiable fused gather -> memory_update -> scatter-back pass
    over the touched rows — see _memory_update_table_pallas and
    docs/KERNELS.md §memory_update_table."""
    return _diff_memory_update_table(block_m, clip, delta_mode, interpret)(
        table, last_t, x, gather_idx, write_idx, times, w, u, b,
        delta_mean, scale, gamma)


# ---------------------------------------------------------------------------
# Standalone Eq. 7 predict fill (the pipelined schedule's staleness fill)
# ---------------------------------------------------------------------------


def _predict_kernel(s_ref, dmean_ref, scale_ref, out_ref, *, clip):
    s = s_ref[...].astype(jnp.float32)
    dmean = dmean_ref[...].astype(jnp.float32)
    out = s + jnp.clip(scale_ref[...] * dmean, -clip, clip)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "clip", "interpret"))
def _pres_predict_pallas(s_prev, delta_mean, scale, *, block_m: int = 256,
                         clip: float = 5.0, interpret: bool = True):
    """s_prev/delta_mean: (M, D), scale: (M,) -> extrapolated rows (M, D)."""
    m, d = s_prev.shape
    s_prev, delta_mean, scale = tiling.pad_rows(
        block_m, s_prev, delta_mean, scale.astype(jnp.float32)[:, None])
    mm = s_prev.shape[0]
    out = pl.pallas_call(
        functools.partial(_predict_kernel, clip=clip),
        grid=(mm // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i: (i, 0)),
        out_shape=tiling.out_struct((mm, d), s_prev.dtype, s_prev,
                                    delta_mean, scale),
        interpret=interpret,
        name="pres_predict",
    )(s_prev, delta_mean, scale)
    return out[:m]


@functools.lru_cache(maxsize=None)
def _diff_predict(block_m: int, clip: float, interpret: bool):
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_pres_predict_pallas, block_m=block_m, clip=clip,
                          interpret=interpret),
        functools.partial(ref.pres_predict_ref, clip=clip))


def pres_predict(s_prev, delta_mean, scale, *, block_m: int = 256,
                 clip: float = 5.0, interpret: bool = True):
    """Differentiable Eq. 7 extrapolation fill."""
    return _diff_predict(block_m, clip, interpret)(s_prev, delta_mean, scale)
