"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gru_cell_ref(x, h, w, u, b):
    """x: (M, Din), h: (M, D), w: (Din, 3D), u: (D, 3D), b: (3D,)."""
    gx = x @ w + b
    gh = h @ u
    d = h.shape[-1]
    rx, zx, nx = gx[..., :d], gx[..., d:2 * d], gx[..., 2 * d:]
    rh, zh, nh = gh[..., :d], gh[..., d:2 * d], gh[..., 2 * d:]
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    return (1 - z) * h + z * n


def rnn_cell_ref(x, h, w, u, b):
    """x: (M, Din), h: (M, D), w: (Din, D), u: (D, D), b: (D,)."""
    return jnp.tanh(x @ w + h @ u + b)


CELL_REFS = {"gru": gru_cell_ref, "rnn": rnn_cell_ref}


def pres_predict_ref(s_prev, delta_mean, dt, clip=5.0):
    """Eq. 7 extrapolation fill: s_prev + clip(dt * delta_mean)."""
    return s_prev + jnp.clip(dt[:, None] * delta_mean, -clip, clip)


def pres_filter_ref(s_prev, s_meas, delta_mean, dt, gamma, clip=5.0,
                    delta_mode="innovation"):
    """Fused predict (Eq. 7) -> correct (Eq. 8) -> delta rate.
    delta_mode: "innovation" (Eq. 9) or "transition" (Alg. 2 variant).
    Returns (fused, delta_rate)."""
    s_pred = pres_predict_ref(s_prev, delta_mean, dt, clip=clip)
    fused = (1.0 - gamma) * s_pred + gamma * s_meas
    base = s_pred if delta_mode == "innovation" else s_prev
    delta = (fused - base) / jnp.maximum(dt, 1.0)[:, None]
    return fused, delta


def memory_update_ref(x, h, w, u, b, delta_mean, scale, gamma, clip=5.0,
                      delta_mode="innovation", cell="gru"):
    """Fused MEMORY maintenance over the touched rows: the memory cell's
    transition (measurement; `cell` "gru" or "rnn") -> Eq. 7 predict ->
    Eq. 8 correct -> delta rate. Returns (s_meas, fused, delta_rate), each
    (M, D)."""
    s_meas = CELL_REFS[cell](x, h, w, u, b)
    fused, delta = pres_filter_ref(h, s_meas, delta_mean, scale, gamma,
                                   clip=clip, delta_mode=delta_mode)
    return s_meas, fused, delta


def memory_update_table_ref(table, last_t, x, gather_idx, write_idx, times,
                            w, u, b, delta_mean, scale, gamma, clip=5.0,
                            delta_mode="innovation", cell="gru"):
    """Fused touched-row pass over the WHOLE memory table: gather the
    previous rows at gather_idx, run memory_update_ref on them, scatter the
    fused rows (and their timestamps) back at write_idx.

    Drop-slot convention (mdgnn.scatter_rows, one row wider here): row
    n_nodes is the dump target for non-selected/masked writes; row
    n_nodes + 1 is an all-zeros source that masked occurrences gather —
    it is never written, so the Pallas kernel's sequential grid and this
    gather-everything-first oracle see identical values at every step
    (callers must order valid occurrences so each node's written occurrence
    comes after all its gathers — mdgnn.occurrence_order).

    Implemented WITHOUT widening the table (the Pallas impl pads; two
    O(N·D) concat copies per step would make the oracle slower than the
    unfused chain it replaces): masked gathers resolve to zeros via a
    clamped gather + where, and the drop-slot write is a scatter with
    mode="drop" — index n falls out of bounds and is discarded.

    Returns (new_table (N, D), new_last_t (N,), s_meas, fused, delta)."""
    n = table.shape[0]
    ok = (gather_idx < n)[:, None]
    h = jnp.where(ok, table[jnp.minimum(gather_idx, n - 1)],
                  0.0).astype(jnp.float32)
    s_meas, fused, delta = memory_update_ref(x, h, w, u, b, delta_mean,
                                             scale, gamma, clip=clip,
                                             delta_mode=delta_mode,
                                             cell=cell)
    new_tab = table.at[write_idx].set(fused.astype(table.dtype),
                                      mode="drop")
    new_lt = last_t.at[write_idx].set(times.astype(last_t.dtype),
                                      mode="drop")
    return new_tab, new_lt, s_meas, fused, delta


def link_score_ref(h_src, h_items, w1, b1, w2, b2):
    """Pairwise link-decoder scores for serving's recommend-topk path.

    h_src: (B, D), h_items: (I, D), w1: (2D, D), b1: (D,), w2: (D, 1),
    b2: (1,) -> (B, I) scores. Row b, column i equals
    mdgnn.link_logits on the pair (h_src[b], h_items[i]): the concatenated
    matmul splits as h_src @ w1[:D] + h_items @ w1[D:], so the (B, I, D)
    hidden layer is formed from two rank-D factors instead of B*I decoder
    calls."""
    d = h_src.shape[-1]
    a = h_src.astype(jnp.float32) @ w1[:d]        # (B, D)
    c = h_items.astype(jnp.float32) @ w1[d:]      # (I, D)
    hidden = jax.nn.relu(a[:, None, :] + c[None, :, :] + b1)
    return (hidden @ w2)[..., 0] + b2[0]


def neighbor_attn_ref(q, k, v, valid):
    """TGN temporal neighbour attention.
    q: (M, E), k/v: (M, K, E), valid: (M, K) bool -> (M, E)."""
    scores = jnp.einsum("me,mke->mk", q, k) / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    probs = jnp.where(jnp.any(valid, -1, keepdims=True), probs, 0.0)
    return jnp.einsum("mk,mke->me", probs.astype(q.dtype), v)


def embed_attn_ref(h_self, tab, idx, dt, valid, tw, tb, wq, wk, wv,
                   n_heads=1):
    """Fused deduplicated embedding layer (the compacted-frontier inner
    loop, docs/KERNELS.md §embed_attn): gather each row's K neighbour
    hidden states from the unique table, time-encode, project Q/K/V, and
    run the masked multi-head neighbour attention.

    h_self: (R, Din_self) parent hidden rows; tab: (U, Din) child unique
    table; idx: (R, K) int32 inverse indices into tab; dt/valid: (R, K);
    tw/tb: (d_time,) time-encoder params; wq: (Din_self, E);
    wk/wv: (Din + d_time, E). Returns the aggregated heads (R, E) — the
    caller applies the output projection.

    The head fold mirrors embeddings.neighbor_attention exactly so both
    routes share this single-head inner loop (neighbor_attn_ref)."""
    r, kk = valid.shape
    h_nbr = tab[idx.reshape(-1)].reshape(r, kk, -1)
    t_enc = jnp.cos(dt[..., None] * tw + tb)
    kv = jnp.concatenate([h_nbr, t_enc], axis=-1)
    q = h_self @ wq
    k = kv @ wk
    v = kv @ wv
    e = q.shape[-1]
    if n_heads > 1:
        dh = e // n_heads
        q = q.reshape(r * n_heads, dh)
        k = (k.reshape(r, kk, n_heads, dh).swapaxes(1, 2)
             .reshape(r * n_heads, kk, dh))
        v = (v.reshape(r, kk, n_heads, dh).swapaxes(1, 2)
             .reshape(r * n_heads, kk, dh))
        valid = jnp.repeat(valid, n_heads, axis=0)
    agg = neighbor_attn_ref(q, k, v, valid)
    if n_heads > 1:
        agg = agg.reshape(r, e)
    return agg


def ssd_chunk_ref(q, k, v, lcum, h0):
    """One SSD / mLSTM chunk (fp32).
    q,k: (L,N), v: (L,P), lcum: (L,) inclusive cumulative log-decay,
    h0: (N,P) carried state. Returns (y (L,P), h1 (N,P))."""
    ltot = lcum[-1]
    scores = q @ k.T                             # (L, L)
    decay = lcum[:, None] - lcum[None, :]
    mask = jnp.tril(jnp.ones(scores.shape, bool))
    sdk = jnp.where(mask, scores * jnp.exp(decay), 0.0)
    y = sdk @ v + (q * jnp.exp(lcum)[:, None]) @ h0
    w = jnp.exp(ltot - lcum)
    h1 = h0 * jnp.exp(ltot) + (k * w[:, None]).T @ v
    return y, h1
