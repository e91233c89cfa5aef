"""Fused PRES predict->correct->innovation Pallas kernel.

The PRES filter is memory-bound elementwise work over the touched memory
rows (Eqs. 7-9). Unfused, it is 6 separate HBM round trips (predict, clip,
fuse, subtract, divide, write); this kernel does one read of
(s_prev, s_meas, delta_mean, dt) and one write of (fused, delta_rate) per
VMEM tile. The GMM gather (mixture mean per node) stays outside — gathers
are XLA's job; the kernel takes the gathered rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _filter_kernel(s_prev_ref, s_meas_ref, dmean_ref, dt_ref, gamma_ref,
                   fused_ref, delta_ref, *, clip, delta_mode):
    s_prev = s_prev_ref[...].astype(jnp.float32)
    s_meas = s_meas_ref[...].astype(jnp.float32)
    dmean = dmean_ref[...].astype(jnp.float32)
    dt = dt_ref[...]
    gamma = gamma_ref[0, 0]
    step = jnp.clip(dt * dmean, -clip, clip)
    s_pred = s_prev + step
    fused = (1.0 - gamma) * s_pred + gamma * s_meas
    base = s_pred if delta_mode == "innovation" else s_prev
    delta = (fused - base) / jnp.maximum(dt, 1.0)
    fused_ref[...] = fused.astype(fused_ref.dtype)
    delta_ref[...] = delta.astype(delta_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "clip", "interpret",
                                             "delta_mode"))
def _pres_filter_pallas(s_prev, s_meas, delta_mean, dt, gamma, *,
                        clip: float = 5.0, block_m: int = 256,
                        interpret: bool = True,
                        delta_mode: str = "innovation"):
    """s_prev/s_meas/delta_mean: (M, D); dt: (M,); gamma: scalar.
    Returns (fused (M, D), delta_rate (M, D))."""
    m, d = s_prev.shape
    s_prev, s_meas, delta_mean, dt = tiling.pad_rows(
        block_m, s_prev, s_meas, delta_mean, dt.astype(jnp.float32)[:, None])
    mm = s_prev.shape[0]
    like = (s_prev, s_meas, delta_mean, dt)
    fused, delta = pl.pallas_call(
        functools.partial(_filter_kernel, clip=clip, delta_mode=delta_mode),
        grid=(mm // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            tiling.SMEM_SCALAR,
        ],
        out_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
        ],
        out_shape=[
            tiling.out_struct((mm, d), s_prev.dtype, *like),
            tiling.out_struct((mm, d), jnp.float32, *like),
        ],
        interpret=interpret,
    )(s_prev, s_meas, delta_mean, dt,
      jnp.reshape(gamma.astype(jnp.float32), (1, 1)))
    return fused[:m], delta[:m]


@functools.lru_cache(maxsize=None)
def _diff_filter(clip: float, block_m: int, interpret: bool, delta_mode: str):
    """Pallas forward, oracle backward (kernels/autodiff.py::oracle_vjp).
    gamma is the learnable Eq. 8 gate, so gradients must flow to it."""
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_pres_filter_pallas, clip=clip, block_m=block_m,
                          interpret=interpret, delta_mode=delta_mode),
        functools.partial(ref.pres_filter_ref, clip=clip,
                          delta_mode=delta_mode))


def pres_filter(s_prev, s_meas, delta_mean, dt, gamma, *, clip: float = 5.0,
                block_m: int = 256, interpret: bool = True,
                delta_mode: str = "innovation"):
    """Differentiable fused PRES filter."""
    return _diff_filter(clip, block_m, interpret, delta_mode)(
        s_prev, s_meas, delta_mean, dt, gamma)
