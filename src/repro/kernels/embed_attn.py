"""Gather-fused temporal-attention Pallas kernel for the deduplicated
embedding path (docs/KERNELS.md §embed_attn).

One grid step processes `block_r` parent frontier rows against all K of
their neighbour slots: the neighbours' layer l-1 hidden rows are DMA'd
STRAIGHT from the child unique table, which stays in HBM
(`memory_space=pl.ANY`, as the 128-lane slab view of kernels/tiling.py),
into a slot-major VMEM tile — the row origins come from the
scalar-prefetched inverse-index array. The tile is then
time-encoded, projected to K/V, and folded slot by slot into an
online-softmax accumulator. HBM never sees the (R, K, E) key/value
tensors the unfused chain materialises — the whole per-layer chain
(gather -> time-encode -> QKV -> masked softmax -> weighted sum) is one
pass.

The K/V projections take the key input [h_nbr, phi(dt)] as two matmuls
against the row blocks of wk/wv (no lane concatenation in VMEM), and the
heads are separated with lane masks instead of head reshapes. `block_r` (autotuned,
kernels/autotune.py::BLOCK_CANDIDATES) trades DMAs in flight per step
against VMEM; R is padded to a multiple, so any block_r is valid for any R.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

NEG_INF = -1e30


def _embed_attn_kernel(idx_ref, hself_ref, tab_ref, dt_ref, valid_ref,
                       tw_ref, tb_ref, wq_ref, wkh_ref, wkt_ref, wvh_ref,
                       wvt_ref, out_ref, buf, sem, *, n_heads, block_r, kk):
    base = pl.program_id(0) * (block_r * kk)
    c = buf.shape[1]
    d_tab = wkh_ref.shape[0]

    def gather(r, j, k):
        return tiling.slab_copy(tab_ref, idx_ref[base + r * kk + j], buf,
                                (j, r), sem.at[0], c, k)

    def start_row(r, carry):
        for j in range(kk):
            for k in range(c):
                gather(r, j, k).start()
        return carry

    def wait_row(r, carry):
        for j in range(kk):
            for k in range(c):
                gather(r, j, k).wait()
        return carry

    jax.lax.fori_loop(0, block_r, start_row, 0)
    jax.lax.fori_loop(0, block_r, wait_row, 0)

    e = wq_ref.shape[-1]
    dh = e // n_heads
    q = jnp.dot(hself_ref[...].astype(jnp.float32), wq_ref[...],
                preferred_element_type=jnp.float32)            # (br, E)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, e), 1)
    heads = [(lane >= hh * dh) & (lane < (hh + 1) * dh)
             for hh in range(n_heads)]                         # (1, E) each
    dt = dt_ref[...]                                           # (br, K)
    ok = valid_ref[...] > 0                                    # (br, K)
    inv_sqrt = 1.0 / jnp.sqrt(float(dh))
    m = [jnp.full((block_r, 1), NEG_INF, jnp.float32)] * n_heads
    l = [jnp.zeros((block_r, 1), jnp.float32)] * n_heads
    acc = [jnp.zeros((block_r, e), jnp.float32)] * n_heads
    for j in range(kk):
        h_nbr = tiling.from_slabs(buf[j], d_tab)               # (br, Din)
        t_enc = jnp.cos(dt[:, j:j + 1] * tw_ref[...] + tb_ref[...])
        k = (jnp.dot(h_nbr, wkh_ref[...], preferred_element_type=jnp.float32)
             + jnp.dot(t_enc, wkt_ref[...],
                       preferred_element_type=jnp.float32))
        v = (jnp.dot(h_nbr, wvh_ref[...], preferred_element_type=jnp.float32)
             + jnp.dot(t_enc, wvt_ref[...],
                       preferred_element_type=jnp.float32))
        okj = ok[:, j:j + 1]                                   # (br, 1)
        qk = q * k
        for hh in range(n_heads):
            s = jnp.sum(jnp.where(heads[hh], qk, 0.0), axis=-1,
                        keepdims=True) * inv_sqrt              # (br, 1)
            s = jnp.where(okj, s, NEG_INF)
            m_new = jnp.maximum(m[hh], s)
            alpha = jnp.exp(m[hh] - m_new)
            # invalid slots contribute exactly 0 even when m_new == NEG_INF
            # (the all-masked prefix, where exp(s - m_new) would be 1)
            p = jnp.where(okj, jnp.exp(s - m_new), 0.0)
            l[hh] = l[hh] * alpha + p
            acc[hh] = acc[hh] * alpha + p * v
            m[hh] = m_new
    # all-masked rows have l == 0 and finalise to exactly 0, matching the
    # oracle's any_valid zeroing
    out = sum(jnp.where(heads[hh], acc[hh] / jnp.maximum(l[hh], 1e-30), 0.0)
              for hh in range(n_heads))
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_heads", "block_r", "interpret"))
def _embed_attn_pallas(h_self, tab, idx, dt, valid, tw, tb, wq, wk, wv, *,
                       n_heads: int = 1, block_r: int = 16,
                       interpret: bool = True):
    """h_self: (R, Din_self), tab: (U, Din), idx: (R, K) int32, dt/valid:
    (R, K), tw/tb: (d_time,), wq: (Din_self, E), wk/wv: (Din + d_time, E)
    -> (R, E) fp32 aggregated heads (see ref.embed_attn_ref)."""
    r, kk = valid.shape
    u, d_tab = tab.shape
    d_self = h_self.shape[1]
    d_time = tw.shape[0]
    e = wq.shape[1]
    h_self, idx, dt, valid = tiling.pad_rows(block_r, h_self, idx, dt, valid)
    rp = h_self.shape[0]
    idx_flat = jnp.clip(idx.reshape(-1), 0, u - 1).astype(jnp.int32)
    row = lambda i, s: (i, 0)
    whole = lambda i, s: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rp // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, d_self), row),                 # h_self
            pl.BlockSpec(memory_space=pl.ANY),                    # tab slabs
            pl.BlockSpec((block_r, kk), row),                     # dt
            pl.BlockSpec((block_r, kk), row),                     # valid
            pl.BlockSpec((1, d_time), whole),                     # tw
            pl.BlockSpec((1, d_time), whole),                     # tb
            pl.BlockSpec((d_self, e), whole),                     # wq
            pl.BlockSpec((d_tab, e), whole),                      # wk[:Din]
            pl.BlockSpec((d_time, e), whole),                     # wk[Din:]
            pl.BlockSpec((d_tab, e), whole),                      # wv[:Din]
            pl.BlockSpec((d_time, e), whole),                     # wv[Din:]
        ],
        out_specs=pl.BlockSpec((block_r, e), row),
        scratch_shapes=[
            pltpu.VMEM((kk, tiling.n_slabs(d_tab), block_r, tiling.LANES),
                       jnp.float32),                              # gathers
            pltpu.SemaphoreType.DMA((1,)),
        ])
    out = pl.pallas_call(
        functools.partial(_embed_attn_kernel, n_heads=n_heads,
                          block_r=block_r, kk=kk),
        grid_spec=grid_spec,
        out_shape=tiling.out_struct((rp, e), jnp.float32, h_self, tab, idx,
                                    dt, valid),
        interpret=interpret,
        name="embed_attn",
    )(idx_flat, h_self, tiling.slab_view(tab), dt.astype(jnp.float32),
      valid.astype(jnp.int32), tw.reshape(1, d_time), tb.reshape(1, d_time), wq,
      wk[:d_tab], wk[d_tab:], wv[:d_tab], wv[d_tab:])
    return out[:r]


@functools.lru_cache(maxsize=None)
def _diff_embed_attn(n_heads: int, block_r: int, interpret: bool):
    """Pallas forward, oracle backward (kernels/autodiff.py::oracle_vjp);
    the int32 inverse indices and the boolean validity mask get no
    cotangent. The table cotangent flows through the oracle's gather
    transpose — exactly the scatter-add the dense path would have run."""
    from repro.kernels import autodiff, ref
    return autodiff.oracle_vjp(
        functools.partial(_embed_attn_pallas, n_heads=n_heads,
                          block_r=block_r, interpret=interpret),
        functools.partial(ref.embed_attn_ref, n_heads=n_heads),
        nondiff=(2, 4))


def embed_attn(h_self, tab, idx, dt, valid, tw, tb, wq, wk, wv, *,
               n_heads: int = 1, block_r: int = 16, interpret: bool = True):
    """Differentiable fused dedup-frontier embedding layer."""
    return _diff_embed_attn(n_heads, block_r, interpret)(
        h_self, tab, idx, dt, valid, tw, tb, wq, wk, wv)
