"""Fused pairwise link-decoder scoring Pallas kernel (serving hot path).

`recommend_topk` scores every (query source, candidate item) pair through
the 2-layer link decoder. Done naively that is a (B, I, D) hidden tensor
materialized in HBM — at production scale (B requests x the full item
memory) the dominant serve-time cost. This kernel tiles the pair grid
(block_b x block_i): each program computes its source/item factor matmuls
on the MXU and keeps the (block_b, block_i, D) hidden activation entirely
in VMEM, writing only the (block_b, block_i) score tile back. One HBM read
of the endpoint embeddings + one write of the scores per tile.

The decomposition matches `kernels/ref.py::link_score_ref` (and therefore
`mdgnn.link_logits` on each pair): concat([h_s, h_i]) @ w1 splits into
h_s @ w1[:D] + h_i @ w1[D:].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _link_score_kernel(hs_ref, hi_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                       out_ref):
    hs = hs_ref[...].astype(jnp.float32)          # (bm, D)
    hi = hi_ref[...].astype(jnp.float32)          # (bi, D)
    w1 = w1_ref[...].astype(jnp.float32)          # (2D, D)
    d = hs.shape[-1]
    a = hs @ w1[:d]                               # (bm, D)  source factor
    c = hi @ w1[d:]                               # (bi, D)  item factor
    hidden = jax.nn.relu(a[:, None, :] + c[None, :, :] + b1_ref[...])
    scores = (hidden @ w2_ref[...].astype(jnp.float32))[..., 0] + b2_ref[0]
    out_ref[...] = scores.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_i",
                                             "interpret"))
def _link_score_pallas(h_src, h_items, w1, b1, w2, b2, *,
                       block_b: int = 32, block_i: int = 128,
                       interpret: bool = True):
    """h_src: (B, D), h_items: (I, D), w1: (2D, D), b1: (D,), w2: (D, 1),
    b2: (1,). Returns (B, I) float32 scores."""
    b, d = h_src.shape
    i = h_items.shape[0]
    block_b = min(block_b, max(b, 1))
    block_i = min(block_i, max(i, 1))
    pad_b, pad_i = (-b) % block_b, (-i) % block_i
    if pad_b:
        h_src = jnp.pad(h_src, ((0, pad_b), (0, 0)))
    if pad_i:
        h_items = jnp.pad(h_items, ((0, pad_i), (0, 0)))
    bb, ii = h_src.shape[0], h_items.shape[0]
    out = pl.pallas_call(
        _link_score_kernel,
        grid=(bb // block_b, ii // block_i),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda m, n: (m, 0)),
            pl.BlockSpec((block_i, d), lambda m, n: (n, 0)),
            pl.BlockSpec((2 * d, d), lambda m, n: (0, 0)),
            pl.BlockSpec((d,), lambda m, n: (0,)),
            pl.BlockSpec((d, 1), lambda m, n: (0, 0)),
            pl.BlockSpec((1,), lambda m, n: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b, block_i), lambda m, n: (m, n)),
        out_shape=jax.ShapeDtypeStruct((bb, ii), jnp.float32),
        interpret=interpret,
        name="link_score",
    )(h_src, h_items, w1.astype(jnp.float32), b1.astype(jnp.float32),
      w2.astype(jnp.float32), b2.astype(jnp.float32))
    return out[:b, :i]


@functools.lru_cache(maxsize=None)
def _diff_link_score(block_b: int, block_i: int, interpret: bool):
    """Pallas forward, oracle backward (kernels/autodiff.py::oracle_vjp) —
    serving never differentiates through scoring, but the registry contract
    (docs/KERNELS.md §Autodiff) keeps every registered kernel usable under
    jax.grad."""
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_link_score_pallas, block_b=block_b,
                          block_i=block_i, interpret=interpret),
        ref.link_score_ref)


def link_score(h_src, h_items, w1, b1, w2, b2, *, block_b: int = 32,
               block_i: int = 128, interpret: bool = True):
    """Differentiable fused pairwise link-decoder scores, (B, I)."""
    return _diff_link_score(block_b, block_i, interpret)(
        h_src, h_items, w1, b1, w2, b2)
