"""Blockwise online-softmax attention Pallas kernel (flash-attention).

The Pallas form of `repro.nn.attention.blockwise_attention`: the kv loop is
the innermost grid dimension; running (max, denom, accumulator) statistics
live in VMEM scratch across kv steps, so HBM sees one read of each (q, k, v)
tile and one write of the output tile. Tiles are MXU-aligned
(q_block x d and kv_block x d panels; d <= 256).

Grid: (batch*heads, n_q_blocks, n_kv_blocks) — the kv dimension iterates
fastest, matching the TPU's sequential grid execution so the VMEM carry is
valid. Causality is handled by masking (blocks fully above the diagonal
contribute nothing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  q_block: int, kv_block: int, n_kv: int, causal: bool,
                  window: int | None, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # (q_block, d)
    k = k_ref[0].astype(jnp.float32)          # (kv_block, d)
    v = v_ref[0].astype(jnp.float32)          # (kv_block, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q_pos = iq * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    (q_block, kv_block), 0)
    k_pos = ik * kv_block + jax.lax.broadcasted_iota(jnp.int32,
                                                     (q_block, kv_block), 1)
    valid = jnp.ones((q_block, kv_block), jnp.bool_)
    if causal:
        valid &= k_pos <= q_pos
    if window is not None:
        valid &= k_pos > q_pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                        # (q_block,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc / jnp.maximum(l_new, 1e-30)[:, None]).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret"))
def _flash_attn_pallas(q, k, v, *, causal: bool = True,
                       window: int | None = None, q_block: int = 128,
                       kv_block: int = 128, interpret: bool = True):
    """q: (G, S, D) with G = batch*q_heads; k, v: (Gkv, T, D) with
    Gkv = batch*kv_heads and G % Gkv == 0 (GQA: the kv BlockSpec maps query
    head g to kv head g // n_rep — no materialized kv expansion).
    Returns (G, S, D) in q.dtype."""
    g, s, d = q.shape
    gkv, t = k.shape[0], k.shape[1]
    assert g % gkv == 0, (g, gkv)
    n_rep = g // gkv
    q_block = min(q_block, s)
    kv_block = min(kv_block, t)
    assert s % q_block == 0 and t % kv_block == 0, (s, t, q_block, kv_block)
    n_q, n_kv = s // q_block, t // kv_block
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _flash_kernel, q_block=q_block, kv_block=kv_block, n_kv=n_kv,
        causal=causal, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(g, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, d),
                         lambda b, i, j: (b // n_rep, j, 0)),
            pl.BlockSpec((1, kv_block, d),
                         lambda b, i, j: (b // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, s, d), q.dtype),
        scratch_shapes=[
            # running softmax statistics, carried across the kv grid dim
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attn",
    )(q, k, v)
    return out


def flash_attn_ref(q, k, v, *, causal: bool = True, window: int | None = None):
    """Dense oracle. q: (G, S, D); k, v: (Gkv, T, D), G % Gkv == 0."""
    d = q.shape[-1]
    s, t = q.shape[1], k.shape[1]
    n_rep = q.shape[0] // k.shape[0]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=0)
        v = jnp.repeat(v, n_rep, axis=0)
    scores = jnp.einsum("gsd,gtd->gst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (d ** 0.5)
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(t)[None, :]
    valid = jnp.ones((s, t), bool)
    if causal:
        valid &= k_pos <= q_pos
    if window is not None:
        valid &= k_pos > q_pos - window
    scores = jnp.where(valid[None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("gst,gtd->gsd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _diff_flash(causal, window, q_block, kv_block, interpret):
    """Pallas forward, oracle backward (kernels/autodiff.py::oracle_vjp)."""
    from repro.kernels import autodiff
    return autodiff.oracle_vjp(
        functools.partial(_flash_attn_pallas, causal=causal, window=window,
                          q_block=q_block, kv_block=kv_block,
                          interpret=interpret),
        functools.partial(flash_attn_ref, causal=causal, window=window))


def flash_attn(q, k, v, *, causal: bool = True, window: int | None = None,
               q_block: int = 128, kv_block: int = 128,
               interpret: bool = True):
    """Differentiable flash attention (Pallas forward, oracle backward)."""
    return _diff_flash(causal, window, q_block, kv_block, interpret)(q, k, v)
