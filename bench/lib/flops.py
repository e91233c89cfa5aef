"""Operations and bytes, computed from shapes.

* `train_step_flops`: the model FLOPs of one training step, the forward
  matrix products counted from the configuration's shapes and times 3 for
  forward and backward (each product's backward is two products of the
  same size). Element-wise work, gathers and sorts are not counted, and
  nothing recomputed is. The embedding's count comes from the
  configuration's module (`embed_flops` in `bench/configs/<config>.py`).
* `embed_attn_cost`, `memory_update_table_cost`: what one forward call of
  each Pallas kernel must compute and move. Bytes count the rows the
  kernel gathers and writes, not the whole node table it takes aliased in
  place, plus its other operands and outputs once.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def matmul(m, k, n) -> float:
    """FLOPs of an (m x k) by (k x n) matrix product."""
    return 2.0 * m * k * n


def memory_stage_flops(model: dict, d_edge: int, occurrences: int) -> float:
    d, dm, dt = model["d_mem"], model["d_msg"], model["d_time"]
    gates = 3 if model["memory_cell"] == "gru" else 1
    msg = (matmul(occurrences, 2 * d + d_edge + dt, dm)
           + matmul(occurrences, dm, dm))
    cell = matmul(occurrences, dm, gates * d) + matmul(occurrences, d,
                                                        gates * d)
    return msg + cell


def decoder_flops(model: dict, pairs: int) -> float:
    e = model["d_embed"]
    return matmul(pairs, 2 * e, e) + matmul(pairs, e, 1)


def train_step_flops(model: dict, d_edge: int, batch: int, arch) -> float:
    """Model FLOPs of one lag-one step on batches of `batch` events with
    one negative each: the memory stage over 2 * batch endpoint
    occurrences, embeddings of 4 * batch rows (`arch`: the configuration's
    module), 2 * batch scored pairs."""
    fwd = (memory_stage_flops(model, d_edge, 2 * batch)
           + arch.embed_flops(model, 4 * batch)
           + decoder_flops(model, 2 * batch))
    return 3.0 * fwd


def embed_attn_cost(model: dict, rows: int) -> tuple:
    """(flops, bytes) of one forward `embed_attn` call over `rows` rows of
    `n_neighbors` neighbours each (depth 1: every row's neighbours are
    memory rows)."""
    d, dt, e, k = model["d_mem"], model["d_time"], model["d_embed"], \
        model["n_neighbors"]
    flops = (matmul(rows, d, e) + 2 * matmul(rows * k, d + dt, e)
             + 2 * 2.0 * rows * k * e)
    moved = (rows * d * F32                      # own rows
             + rows * k * d * F32                # gathered neighbour rows
             + rows * k * (I32 + F32 + I32)      # index, dt, valid
             + (d * e + 2 * (d + dt) * e + 2 * dt) * F32   # weights
             + rows * e * F32)                   # output
    return flops, float(moved)


def memory_update_table_cost(model: dict, occurrences: int,
                             written: float) -> tuple:
    """(flops, bytes) of one forward `memory_update_table` call over
    `occurrences` endpoint occurrences, `written` of which write their row
    back (one per distinct node)."""
    d, dm = model["d_mem"], model["d_msg"]
    flops = matmul(occurrences, dm, 3 * d) + matmul(occurrences, d, 3 * d)
    moved = (occurrences * d * F32              # gathered rows
             + written * d * F32                # written rows
             + occurrences * dm * F32           # messages
             + occurrences * d * F32            # mixture means
             + occurrences * (F32 + F32 + 2 * I32)   # valid, scale, indices
             + (dm + d + 1) * 3 * d * F32       # weights
             + 3 * occurrences * d * F32)       # s_meas, fused, delta
    return flops, float(moved)
