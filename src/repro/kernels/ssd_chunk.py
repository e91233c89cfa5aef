"""SSD / mLSTM chunk Pallas kernel — the per-chunk heavy math of the
chunked linear recurrence (repro.nn.ssm.chunked_linear_rnn):

    y   = ((q k^T) * exp(lcum_i - lcum_j) [j<=i]) v  +  (q * exp(lcum)) h0
    h1  = exp(ltot) h0 + (k * exp(ltot - lcum))^T v

Grid: one program per (batch*head). Everything for a chunk (L x N keys,
L x P values, the L x L decay-masked score matrix) fits VMEM for L<=256,
N,P<=128 — all three matmuls run on the MXU without touching HBM between
them. The sequential inter-chunk scan stays outside (it is O(S/L) steps of
O(NP) work — bandwidth-trivial).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LOG_EPS = -30.0


def _ssd_kernel(q_ref, k_ref, v_ref, lcum_ref, h0_ref, y_ref, h1_ref):
    q = q_ref[0].astype(jnp.float32)        # (L, N)
    k = k_ref[0].astype(jnp.float32)        # (L, N)
    v = v_ref[0].astype(jnp.float32)        # (L, P)
    lcum = lcum_ref[0].astype(jnp.float32)  # (L,)
    h0 = h0_ref[0].astype(jnp.float32)      # (N, P)
    l = q.shape[0]
    ltot = lcum[l - 1]
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    decay = lcum[:, None] - lcum[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    mask = col <= row
    sdk = jnp.where(mask, scores * jnp.exp(jnp.where(mask, decay, LOG_EPS)), 0.0)
    y = jnp.dot(sdk, v, preferred_element_type=jnp.float32)
    y = y + jnp.dot(q * jnp.exp(lcum)[:, None], h0,
                    preferred_element_type=jnp.float32)
    w = jnp.exp(ltot - lcum)
    h1 = h0 * jnp.exp(ltot) + jnp.dot((k * w[:, None]).T, v,
                                      preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    h1_ref[0] = h1.astype(h1_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_chunk_pallas(q, k, v, lcum, h0, *, interpret: bool = True):
    """Batched chunk step. q,k: (G, L, N); v: (G, L, P); lcum: (G, L);
    h0: (G, N, P) where G = batch*heads. Returns (y (G,L,P), h1 (G,N,P))."""
    g, l, n = q.shape
    p = v.shape[-1]
    y, h1 = pl.pallas_call(
        _ssd_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, l, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l), lambda i: (i, 0)),
            pl.BlockSpec((1, n, p), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, l, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, p), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, l, p), jnp.float32),
            jax.ShapeDtypeStruct((g, n, p), jnp.float32),
        ],
        interpret=interpret,
        name="ssd_chunk",
    )(q, k, v, lcum, h0)
    return y, h1


@functools.lru_cache(maxsize=None)
def _diff_ssd(interpret: bool):
    """Pallas forward, oracle backward (kernels/autodiff.py::oracle_vjp)."""
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_ssd_chunk_pallas, interpret=interpret),
        jax.vmap(ref.ssd_chunk_ref))


def ssd_chunk(q, k, v, lcum, h0, *, interpret: bool = True):
    """Differentiable batched SSD chunk step."""
    return _diff_ssd(interpret)(q, k, v, lcum, h0)
