"""Layout helpers shared by the Pallas kernels: what Mosaic (the TPU's
kernel compiler) accepts for blocks and for manual row DMAs.

* Row tiles: the last two dims of a block must be multiples of (8, 128)
  or equal the array's dims, so kernels tile rows in multiples of 8, carry
  per-row scalars as (M, 1) columns and scalars in SMEM.
* Row gathers: a DMA may only move whole 128-lane slabs. A row of width D
  is therefore stored as `c = ceil(D / 128)` consecutive 128-lane slabs of
  a float32 (N * c, 128) view (`slab_view`); a kernel copies slab k of row
  r with `slab_copy` and reassembles the row in VMEM with `from_slabs`.
  At D = 128 in float32 the view is a free reshape; otherwise it costs one
  pad/cast pass over the table on the way in and one on the way out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SMEM_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)


def out_struct(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """A kernel output's shape, varying over the same mesh axes as the
    inputs `like` — what pallas_call needs inside a `jax.shard_map` with
    its replication check on (outside one the set is empty)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def pad_rows(block: int, *arrays):
    """Zero-pad every array's leading axis to a multiple of `block`."""
    pad = (-arrays[0].shape[0]) % block
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)


def n_slabs(d: int) -> int:
    return -(-d // LANES)


def slab_view(table):
    """(N, D) table -> float32 (N * c, 128) view, row r at slabs
    [r * c, r * c + c) with the lanes past D zero."""
    n, d = table.shape
    c = n_slabs(d)
    tab = table.astype(jnp.float32)
    if c * LANES != d:
        tab = jnp.pad(tab, ((0, 0), (0, c * LANES - d)))
    return tab.reshape(n * c, LANES)


def from_slab_view(view, n: int, d: int, dtype):
    """Inverse of slab_view."""
    return view.reshape(n, -1)[:, :d].astype(dtype)


def slab_copy(src_ref, src_row, dst_ref, dst_idx, sem, c: int, k: int,
              *, to_table: bool = False):
    """DMA descriptor moving slab k of one row between a slab view in HBM
    (row `src_row`) and a VMEM slab buffer `dst_ref` of shape
    (c, rows, 128) (or any leading index prefix, `dst_idx`, that ends in
    such a buffer). `to_table` reverses the direction (VMEM -> HBM)."""
    hbm = src_ref.at[pl.ds(src_row * c + k, 1)]
    vmem = dst_ref.at[(*dst_idx[:-1], k, pl.ds(dst_idx[-1], 1))]
    if to_table:
        return pltpu.make_async_copy(vmem, hbm, sem)
    return pltpu.make_async_copy(hbm, vmem, sem)


def from_slabs(slabs, d: int):
    """VMEM value (c, rows, 128) -> (rows, d) float32 rows."""
    c = slabs.shape[0]
    rows = slabs[0] if c == 1 else jnp.concatenate(
        [slabs[k] for k in range(c)], axis=-1)
    return rows[:, :d].astype(jnp.float32)


def to_slabs(rows, c: int):
    """(rows, d) value -> list of c (rows, 128) slabs, zero past d."""
    d = rows.shape[-1]
    if c * LANES != d:
        rows = jnp.concatenate(
            [rows, jnp.zeros((rows.shape[0], c * LANES - d), rows.dtype)],
            axis=-1)
    return [rows[:, k * LANES:(k + 1) * LANES] for k in range(c)]
