"""Operation and byte counts, checked by hand at small shapes."""
from bench.lib import cell, flops

TGN = {"variant": "tgn", "memory_cell": "gru", "d_mem": 4, "d_msg": 3,
       "d_time": 2, "d_embed": 4, "n_neighbors": 5}
RNN = dict(TGN, memory_cell="rnn")


def test_memory_stage_flops_by_hand():
    # message: (6 x (2*4 + 7 + 2)) @ (17 x 3), then (6 x 3) @ (3 x 3)
    msg = 2 * 6 * 17 * 3 + 2 * 6 * 3 * 3
    gru = 2 * 6 * 3 * 12 + 2 * 6 * 4 * 12      # x W and h U, 3 gates
    rnn = 2 * 6 * 3 * 4 + 2 * 6 * 4 * 4
    assert flops.memory_stage_flops(TGN, 7, 6) == msg + gru
    assert flops.memory_stage_flops(RNN, 7, 6) == msg + rnn


def test_embed_and_step_flops_by_hand():
    tgn = cell.config_module("tgn-pres")
    rows = 8
    q = 2 * 8 * 4 * 4
    kv = 2 * (2 * 40 * 6 * 4)
    attn = 2 * 2 * 40 * 4
    out = 2 * 8 * 8 * 4
    assert tgn.embed_flops(TGN, rows) == q + kv + attn + out
    dec = 2 * 4 * 8 * 4 + 2 * 4 * 4 * 1
    assert flops.decoder_flops(TGN, 4) == dec
    step = 3 * (flops.memory_stage_flops(TGN, 7, 4)
                + tgn.embed_flops(TGN, 8) + flops.decoder_flops(TGN, 4))
    assert flops.train_step_flops(TGN, 7, 2, tgn) == step


def test_embed_attn_bytes_count_gathered_rows_not_the_table():
    f, b = flops.embed_attn_cost(TGN, 8)
    assert f == 2 * 8 * 4 * 4 + 2 * (2 * 40 * 6 * 4) + 2 * 2 * 40 * 4
    gathered = 40 * 4 * 4
    own = 8 * 4 * 4
    side = 40 * 12
    weights = (16 + 2 * 6 * 4 + 4) * 4
    out = 8 * 4 * 4
    assert b == own + gathered + side + weights + out
    # the cost takes no node count: the aliased table is never charged
    # whole, only the rows the kernel gathers
    assert flops.embed_attn_cost(TGN, 16)[1] < 2 * b + weights


def test_memory_update_table_bytes_count_rows_moved():
    f, b = flops.memory_update_table_cost(TGN, 6, 3)
    assert f == 2 * 6 * 3 * 12 + 2 * 6 * 4 * 12
    expect = (6 * 16 + 3 * 16 + 6 * 12 + 6 * 16 + 6 * 16
              + (3 + 4 + 1) * 12 * 4 + 3 * 6 * 16)
    assert b == expect
    # one more written row costs one row of bytes, whatever the table size
    assert flops.memory_update_table_cost(TGN, 6, 4)[1] - b == 16
