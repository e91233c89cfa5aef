"""TGN's part of the plain reference, for the configuration `tgn-pres`
(`tgn-pres.json` beside this file): what differs from the other MDGNN
variants. The shared reference, `bench/lib/reference.py`, calls it; like
the reference, it imports nothing of the program.

EMBEDDING (TGN, Rossi et al. 2020, with TGL's one layer): two-head
attention over the K most recent neighbours, the query from the node's own
memory row, keys and values from [s_nbr, cos(dt w + b)], then
relu([agg, s_self] Wo). A departure of the repository's architecture from
TGN and TGL, which this reference shares because it checks the program:
the keys and values do not include edge features.

TGN keeps no table beyond the shared node state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.lib.flops import matmul
from bench.lib.reference import time_enc

# model keys the program has no field for, at the only value it can run
NOT_TAKEN = {"dropout": 0.0}
# the module's own tables that must match entry for entry: none
EXACT = ()


def emb_shapes(m: dict, d_edge: int) -> dict:
    """The `emb` subtree of the weights, by the names the program's tree
    uses."""
    d_mem, d_time, d_emb = m["d_mem"], m["d_time"], m["d_embed"]
    return {"l0": {"wq": (d_mem, d_emb),
                   "wk": (d_mem + d_time, d_emb),
                   "wv": (d_mem + d_time, d_emb),
                   "wo": (d_emb + d_mem, d_emb)}}


def embed(m, params, mem, last, state, rows, t_query, dtype):
    """Embeddings of `rows` at times `t_query` from the memory `mem`."""
    lp = params["emb"]["l0"]
    nbr, nbr_t = state["nbr"][rows], state["nbr_t"][rows]
    valid = nbr >= 0
    h_self = mem[rows]
    h_nbr = mem[jnp.maximum(nbr, 0)]
    t_enc = time_enc(params["time"], t_query[:, None] - nbr_t, dtype)
    kv = jnp.concatenate([h_nbr, t_enc], axis=-1)
    q, k, v = h_self @ lp["wq"], kv @ lp["wk"], kv @ lp["wv"]
    heads = m["n_heads"]
    r, kk, e = k.shape
    dh = e // heads
    q = q.reshape(r, heads, dh)
    k = k.reshape(r, kk, heads, dh)
    v = v.reshape(r, kk, heads, dh)
    score = jnp.einsum("rhd,rkhd->rhk", q, k) / math.sqrt(dh)
    score = jnp.where(valid[:, None, :], score, -1e30)
    prob = jax.nn.softmax(score.astype(jnp.float32), axis=-1).astype(dtype)
    prob = jnp.where(jnp.any(valid, -1)[:, None, None], prob, 0)
    agg = jnp.einsum("rhk,rkhd->rhd", prob, v).reshape(r, e)
    return jax.nn.relu(jnp.concatenate([agg, h_self], axis=-1) @ lp["wo"])


def extra_state(n_nodes: int, m: dict, dtype) -> dict:
    return {}


def maintain_extra(m, params, state, new, prev, occ, dtype) -> dict:
    return {}


def embed_flops(m: dict, rows: int) -> float:
    """Forward matmul FLOPs of embedding `rows` rows: the query, keys and
    values, the scores and weighted sum, and the output layer."""
    d, dt, e, k = m["d_mem"], m["d_time"], m["d_embed"], m["n_neighbors"]
    q = matmul(rows, d, e)
    kv = 2 * matmul(rows * k, d + dt, e)
    attn = 2 * 2.0 * rows * k * e          # scores and weighted sum
    out = matmul(rows, e + d, e)
    return q + kv + attn + out
