"""TGN temporal-neighbour attention Pallas kernel (EMBEDDING hot-spot).

Each query row attends over its K ring-buffer neighbours: scores = q.k,
masked softmax, weighted sum of values — a small-batch flash-attention-like
pattern. One VMEM tile holds BM query rows with their (BM, K, E) keys and
values; softmax stays in registers/VMEM, so HBM sees exactly one read of
(q, k, v, mask) and one write of the output per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, valid_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)          # (BM, E)
    k = k_ref[...].astype(jnp.float32)          # (BM, K, E)
    v = v_ref[...].astype(jnp.float32)          # (BM, K, E)
    valid = valid_ref[...]                      # (BM, K) int32 (bool-ish)
    e = q.shape[-1]
    scores = jnp.einsum("me,mke->mk", q, k) / jnp.sqrt(float(e))
    scores = jnp.where(valid > 0, scores, NEG_INF)
    smax = jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores - smax)
    denom = jnp.sum(probs, axis=-1, keepdims=True)
    probs = probs / jnp.maximum(denom, 1e-30)
    any_valid = jnp.sum(valid, axis=-1, keepdims=True) > 0
    probs = jnp.where(any_valid, probs, 0.0)
    out_ref[...] = jnp.einsum("mk,mke->me", probs, v).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _neighbor_attn_pallas(q, k, v, valid, *, block_m: int = 128,
                          interpret: bool = True):
    """q: (M, E); k, v: (M, K, E); valid: (M, K) bool -> (M, E)."""
    m, e = q.shape
    kk = k.shape[1]
    pad_m = (-m) % block_m
    if pad_m:
        q = jnp.pad(q, ((0, pad_m), (0, 0)))
        k = jnp.pad(k, ((0, pad_m), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad_m), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, pad_m), (0, 0)))
    mm = q.shape[0]
    out = pl.pallas_call(
        _attn_kernel,
        grid=(mm // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, e), lambda i: (i, 0)),
            pl.BlockSpec((block_m, kk, e), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_m, kk, e), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_m, kk), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, e), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mm, e), q.dtype),
        interpret=interpret,
        name="neighbor_attn",
    )(q, k, v, valid.astype(jnp.int32))
    return out[:m]


@functools.lru_cache(maxsize=None)
def _diff_attn(block_m: int, interpret: bool):
    """Pallas forward, oracle backward (kernels/autodiff.py::oracle_vjp);
    the boolean validity mask gets no cotangent."""
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_neighbor_attn_pallas, block_m=block_m,
                          interpret=interpret),
        ref.neighbor_attn_ref, nondiff=(3,))


def neighbor_attn(q, k, v, valid, *, block_m: int = 128,
                  interpret: bool = True):
    """Differentiable temporal-neighbour attention."""
    return _diff_attn(block_m, interpret)(q, k, v, valid)
