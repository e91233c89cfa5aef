"""JODIE's part of the plain reference, for the configuration `jodie-pres`
(`jodie-pres.json` beside this file): what differs from the other MDGNN
variants. The shared reference, `bench/lib/reference.py`, calls it; like
the reference, it imports nothing of the program.

MEMORY: the RNN cell, tanh(m W + s U + b) (JODIE, Kumar et al. 2019; TGL
config/JODIE.yml `memory_update: rnn`), which the shared reference already
computes for `memory_cell` "rnn".

EMBEDDING (TGL's identity GNN with `JODIETimeEmbedding`): the updated
memory row projected by the time since its last update,
h = s * (1 + (D w + b)), D = (t - t_last) / (t + 1), with the Linear(1, d)
weight w and bias b. D is computed in float32, as every time is.

Departures of the repository's architecture from JODIE and TGL, which
this reference shares because it checks the program: the mail passes the
repository's two-layer message MLP before the RNN (TGL feeds the raw mail
to the RNN); there is no dropout; the shared engine keeps the neighbour
ring, which JODIE never reads. The benchmark's weights start the
projection's bias at 0 (the harness's init), where TGL draws it N(0, 1).

JODIE keeps no table beyond the shared node state.

Also here, beside the benchmark: the operations and bytes of one call of
the fused table kernel's RNN form, `memory_update_table_rnn`.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.lib.flops import F32, I32, matmul

# model keys the program has no field for, at the only value it can run
NOT_TAKEN = {"dropout": 0.0}
# the module's own tables that must match entry for entry: none
EXACT = ()


def emb_shapes(m: dict, d_edge: int) -> dict:
    """The `emb` subtree of the weights, by the names the program's tree
    uses: the Linear(1, d_mem) weight, stored (in, out), and its bias."""
    return {"l0": {"w": (1, m["d_mem"]), "b": (m["d_mem"],)}}


def embed(m, params, mem, last, state, rows, t_query, dtype):
    """Embeddings of `rows` at times `t_query` from the memory `mem` and
    the last-update times `last`."""
    lp = params["emb"]["l0"]
    d = ((t_query - last[rows]) / (t_query + 1.0)).astype(jnp.float32)
    return mem[rows] * (1 + (d[:, None].astype(dtype) * lp["w"][0]
                             + lp["b"]))


def extra_state(n_nodes: int, m: dict, dtype) -> dict:
    return {}


def maintain_extra(m, params, state, new, prev, occ, dtype) -> dict:
    return {}


def embed_flops(m: dict, rows: int) -> float:
    """Forward matmul FLOPs of embedding `rows` rows: the Linear(1, d_mem)
    of each row's elapsed time."""
    return matmul(rows, 1, m["d_mem"])


def memory_update_table_rnn_cost(model: dict, occurrences: int,
                                 written: float, gates: int = 1) -> tuple:
    """(flops, bytes) of one forward `memory_update_table_rnn` call over
    `occurrences` endpoint occurrences, `written` of which write their row
    back: the RNN's one gate, x W and h U, where the GRU form has three
    (`gates` 3 gives bench/lib/flops.py::memory_update_table_cost). Bytes
    count the rows gathered and written, not the aliased table."""
    d, dm = model["d_mem"], model["d_msg"]
    flops = matmul(occurrences, dm, gates * d) + matmul(occurrences, d,
                                                        gates * d)
    moved = (occurrences * d * F32              # gathered rows
             + written * d * F32                # written rows
             + occurrences * dm * F32           # messages
             + occurrences * d * F32            # mixture means
             + occurrences * (F32 + F32 + 2 * I32)   # valid, scale, indices
             + (dm + d + 1) * gates * d * F32   # weights
             + 3 * occurrences * d * F32)       # s_meas, fused, delta
    return flops, float(moved)
