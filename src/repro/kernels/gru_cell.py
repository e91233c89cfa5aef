"""Fused GRU memory-cell Pallas kernel (the MEMORY module hot-spot).

TPU adaptation of the GPU per-row scatter update: both matmuls (x@W, h@U)
hit the MXU back-to-back while gates stay resident in VMEM — one HBM round
trip for the whole cell instead of 6+ for the unfused jnp version. Rows are
tiled in blocks of BM=128 (grid over rows); the weight panels (Din x 3D,
D x 3D) are kept whole in VMEM (MDGNN memory dims are 100-512, so the panels
are <= a few MB and 128-aligned after padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _gru_kernel(x_ref, h_ref, w_ref, u_ref, b_ref, out_ref):
    x = x_ref[...]
    h = h_ref[...]
    gx = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32) + b_ref[...]
    gh = jnp.dot(h, u_ref[...], preferred_element_type=jnp.float32)
    d = h.shape[-1]
    rx, zx, nx = gx[:, :d], gx[:, d:2 * d], gx[:, 2 * d:]
    rh, zh, nh = gh[:, :d], gh[:, d:2 * d], gh[:, 2 * d:]
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    out_ref[...] = ((1.0 - z) * h + z * n).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _gru_cell_pallas(x, h, w, u, b, *, block_m: int = 128,
                     interpret: bool = True):
    """x: (M, Din), h: (M, D), w: (Din, 3D), u: (D, 3D), b: (3D,)."""
    m, din = x.shape
    d = h.shape[-1]
    pad_m = (-m) % block_m
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
        h = jnp.pad(h, ((0, pad_m), (0, 0)))
    mm = x.shape[0]
    out = pl.pallas_call(
        _gru_kernel,
        grid=(mm // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, din), lambda i: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((din, 3 * d), lambda i: (0, 0)),
            pl.BlockSpec((d, 3 * d), lambda i: (0, 0)),
            pl.BlockSpec((3 * d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda i: (i, 0)),
        out_shape=tiling.out_struct((mm, d), h.dtype, x, h),
        interpret=interpret,
    )(x, h, w, u, b)
    return out[:m]


@functools.lru_cache(maxsize=None)
def _diff_gru(block_m: int, interpret: bool):
    """Pallas forward, oracle backward (kernels/autodiff.py::oracle_vjp)."""
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_gru_cell_pallas, block_m=block_m,
                          interpret=interpret),
        ref.gru_cell_ref)


def gru_cell(x, h, w, u, b, *, block_m: int = 128, interpret: bool = True):
    """Differentiable fused GRU cell (Pallas forward, oracle backward)."""
    return _diff_gru(block_m, interpret)(x, h, w, u, b)
