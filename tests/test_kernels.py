"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
oracle in repro.kernels.ref, swept over shapes and dtypes."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


# ---------------------------------------------------------------------------
# gru_cell
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b", [1, 7, 128, 300])
@pytest.mark.parametrize("d", [32, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gru_cell_matches_ref(b, d, dtype):
    rng = np.random.default_rng(b * 1000 + d)
    x = jnp.asarray(rng.normal(size=(b, d)), dtype)
    h = jnp.asarray(rng.normal(size=(b, d)), dtype)
    w = jnp.asarray(rng.normal(size=(d, 3 * d)) * 0.1, dtype)
    u = jnp.asarray(rng.normal(size=(d, 3 * d)) * 0.1, dtype)
    bias = jnp.asarray(rng.normal(size=(3 * d,)) * 0.01, dtype)
    out = ops.gru_cell(x, h, w, u, bias, interpret=True)
    want = ref.gru_cell_ref(x, h, w, u, bias)
    assert out.shape == (b, d)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_gru_cell_output_bounded():
    """GRU output is a convex combination of h and tanh(.) — bounded by
    max(|h|, 1)."""
    rng = np.random.default_rng(0)
    b, d = 64, 64
    x = jnp.asarray(rng.normal(size=(b, d)) * 10, jnp.float32)
    h = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, 3 * d)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(d, 3 * d)), jnp.float32)
    bias = jnp.zeros((3 * d,), jnp.float32)
    out = ops.gru_cell(x, h, w, u, bias, interpret=True)
    bound = jnp.maximum(jnp.abs(h), 1.0) + 1e-6
    assert bool(jnp.all(jnp.abs(out) <= bound))


def test_gru_cell_agrees_with_model_cell():
    """The Pallas kernel must agree with the MDGNN module's GRU (they are the
    two implementations the config flag `use_kernels` switches between)."""
    from repro.models import modules
    from repro.nn.module import ParamBuilder

    rng = np.random.default_rng(3)
    d = 96
    b = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
    modules.gru_init(b, "mem", d, d)
    p = b.params["mem"]
    x = jnp.asarray(rng.normal(size=(33, d)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(33, d)), jnp.float32)
    want = modules.gru_cell(p, x, h)
    got = ops.gru_cell(x, h, p["w"], p["u"], p["b"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# pres_filter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 64, 200])
@pytest.mark.parametrize("d", [16, 128])
def test_pres_filter_matches_ref(n, d):
    rng = np.random.default_rng(n + d)
    s_prev = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    s_meas = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    dm = jnp.asarray(rng.normal(size=(n, d)) * 0.01, jnp.float32)
    dt = jnp.abs(jnp.asarray(rng.normal(size=(n,)), jnp.float32))
    gamma = jnp.asarray(0.3, jnp.float32)
    got = ops.pres_filter(s_prev, s_meas, dm, dt, gamma, interpret=True)
    want = ref.pres_filter_ref(s_prev, s_meas, dm, dt, gamma)
    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got_l) == len(want_l)
    for g, w in zip(got_l, want_l):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_pres_filter_gamma_extremes():
    """gamma=1 -> pure measurement; gamma=0 -> pure (clipped) prediction."""
    rng = np.random.default_rng(9)
    n, d = 32, 32
    s_prev = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    s_meas = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    dm = jnp.zeros((n, d), jnp.float32)
    dt = jnp.ones((n,), jnp.float32)
    out1 = ref.pres_filter_ref(s_prev, s_meas, dm, dt, jnp.asarray(1.0))
    fused1 = jax.tree.leaves(out1)[0]
    np.testing.assert_allclose(np.asarray(fused1), np.asarray(s_meas), atol=1e-6)
    out0 = ref.pres_filter_ref(s_prev, s_meas, dm, dt, jnp.asarray(0.0))
    fused0 = jax.tree.leaves(out0)[0]
    # zero delta-mean => prediction == s_prev
    np.testing.assert_allclose(np.asarray(fused0), np.asarray(s_prev), atol=1e-6)


@pytest.mark.parametrize("delta_mode", ["innovation", "transition"])
def test_pres_filter_delta_modes_match_ref(delta_mode):
    rng = np.random.default_rng(31)
    n, d = 100, 48
    s_prev = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    s_meas = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    dm = jnp.asarray(rng.normal(size=(n, d)) * 0.01, jnp.float32)
    dt = jnp.abs(jnp.asarray(rng.normal(size=(n,)), jnp.float32))
    gamma = jnp.asarray(0.3, jnp.float32)
    got = ops.pres_filter(s_prev, s_meas, dm, dt, gamma, interpret=True,
                          delta_mode=delta_mode)
    want = ref.pres_filter_ref(s_prev, s_meas, dm, dt, gamma,
                               delta_mode=delta_mode)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)
    # the two modes genuinely differ on the delta output
    other = ref.pres_filter_ref(
        s_prev, s_meas, dm, dt, gamma,
        delta_mode="transition" if delta_mode == "innovation" else "innovation")
    assert float(jnp.abs(want[1] - other[1]).max()) > 1e-3


# ---------------------------------------------------------------------------
# pres_predict (the pipelined schedule's staleness fill)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d", [(1, 16), (200, 64), (400, 32)])
def test_pres_predict_matches_ref(n, d):
    rng = np.random.default_rng(n + d)
    s_prev = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    dm = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    scale = jnp.abs(jnp.asarray(rng.normal(size=(n,)) * 3, jnp.float32))
    got = ops.pres_predict(s_prev, dm, scale, interpret=True, clip=1.0)
    want = ref.pres_predict_ref(s_prev, dm, scale, clip=1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # clip engaged for at least some rows at this magnitude
    assert float(jnp.abs(got - s_prev).max()) <= 1.0 + 1e-6


def test_pres_predict_gradients_match_oracle():
    rng = np.random.default_rng(33)
    n, d = 64, 32
    args = [jnp.asarray(rng.normal(size=(n, d)) * 0.3, jnp.float32),
            jnp.asarray(rng.normal(size=(n, d)) * 0.1, jnp.float32),
            jnp.abs(jnp.asarray(rng.normal(size=(n,)), jnp.float32))]
    gk = jax.grad(lambda *a: jnp.sum(
        ops.pres_predict(*a, interpret=True) ** 2), argnums=(0, 1, 2))(*args)
    gr = jax.grad(lambda *a: jnp.sum(
        ref.pres_predict_ref(*a) ** 2), argnums=(0, 1, 2))(*args)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# memory_update (fused GRU + PRES filter + delta-rate)
# ---------------------------------------------------------------------------


def _memory_update_args(rng, m, d):
    return (jnp.asarray(rng.normal(size=(m, d)), jnp.float32),        # x
            jnp.asarray(rng.normal(size=(m, d)), jnp.float32),        # h
            jnp.asarray(rng.normal(size=(d, 3 * d)) * 0.1, jnp.float32),
            jnp.asarray(rng.normal(size=(d, 3 * d)) * 0.1, jnp.float32),
            jnp.asarray(rng.normal(size=(3 * d,)) * 0.01, jnp.float32),
            jnp.asarray(rng.normal(size=(m, d)) * 0.01, jnp.float32),  # dmean
            jnp.abs(jnp.asarray(rng.normal(size=(m,)), jnp.float32)),  # scale
            jnp.asarray(0.4, jnp.float32))                             # gamma


@pytest.mark.parametrize("m", [1, 64, 300])
@pytest.mark.parametrize("delta_mode", ["innovation", "transition"])
def test_memory_update_matches_ref(m, delta_mode):
    rng = np.random.default_rng(m)
    args = _memory_update_args(rng, m, 32)
    got = ops.memory_update(*args, interpret=True, clip=1.0,
                            delta_mode=delta_mode)
    want = ref.memory_update_ref(*args, clip=1.0, delta_mode=delta_mode)
    assert len(got) == 3
    for g, w in zip(got, want):
        assert g.shape == (m, 32)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_memory_update_matches_composed_kernels():
    """The fused kernel must equal gru_cell followed by pres_filter — the
    two-kernel chain it replaces."""
    rng = np.random.default_rng(41)
    args = _memory_update_args(rng, 128, 48)
    x, h, w, u, b, dm, scale, gamma = args
    s_meas, fused, delta = ops.memory_update(*args, interpret=True, clip=1.0)
    s_meas2 = ops.gru_cell(x, h, w, u, b, interpret=True)
    fused2, delta2 = ops.pres_filter(h, s_meas2, dm, scale, gamma,
                                     interpret=True, clip=1.0)
    np.testing.assert_allclose(np.asarray(s_meas), np.asarray(s_meas2),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(fused2),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(delta), np.asarray(delta2),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# memory_update_table (fused gather -> memory_update -> scatter-back)
# ---------------------------------------------------------------------------


def _memory_update_table_args(rng, n, m, d, pad_frac=0.2, gates=3):
    """Args in the kernel's required occurrence order (the layout
    mdgnn.occurrence_order produces): valid occurrences grouped by node,
    each group's last occurrence selected (written), masked occurrences
    at the end gathering the all-zeros row n + 1. `gates`: 3 for the GRU
    cell's weights, 1 for the RNN's."""
    n_valid = m - int(m * pad_frac)
    nodes = np.sort(rng.integers(0, n, size=n_valid))
    last = np.r_[nodes[:-1] != nodes[1:], True]
    gidx = np.r_[nodes, np.full(m - n_valid, n + 1)]
    widx = np.r_[np.where(last, nodes, n), np.full(m - n_valid, n)]
    return (jnp.asarray(rng.normal(size=(n, d)), jnp.float32),   # table
            jnp.abs(jnp.asarray(rng.normal(size=(n,)), jnp.float32)),
            jnp.asarray(rng.normal(size=(m, d)), jnp.float32),   # x
            jnp.asarray(gidx, jnp.int32), jnp.asarray(widx, jnp.int32),
            jnp.abs(jnp.asarray(rng.normal(size=(m,)), jnp.float32)),  # times
            jnp.asarray(rng.normal(size=(d, gates * d)) * 0.1, jnp.float32),
            jnp.asarray(rng.normal(size=(d, gates * d)) * 0.1, jnp.float32),
            jnp.asarray(rng.normal(size=(gates * d,)) * 0.01, jnp.float32),
            jnp.asarray(rng.normal(size=(m, d)) * 0.01, jnp.float32),
            jnp.abs(jnp.asarray(rng.normal(size=(m,)), jnp.float32)),
            jnp.asarray(0.4, jnp.float32))                       # gamma


@pytest.mark.parametrize("n,m", [(20, 1), (50, 64), (300, 200)])
@pytest.mark.parametrize("delta_mode", ["innovation", "transition"])
def test_memory_update_table_matches_ref(n, m, delta_mode):
    rng = np.random.default_rng(n + m)
    args = _memory_update_table_args(rng, n, m, 32)
    got = ops.memory_update_table(*args, interpret=True, clip=1.0,
                                  delta_mode=delta_mode)
    want = ref.memory_update_table_ref(*args, clip=1.0,
                                       delta_mode=delta_mode)
    assert len(got) == 5
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_memory_update_table_untouched_rows_preserved():
    """Rows never written must come back bit-identical (the aliased table
    is updated in place, not rebuilt)."""
    rng = np.random.default_rng(55)
    n, m, d = 60, 40, 16
    args = _memory_update_table_args(rng, n, m, d)
    table, widx = args[0], args[4]
    new_tab, new_lt, *_ = ops.memory_update_table(*args, interpret=True)
    touched = set(np.asarray(widx).tolist()) - {n, n + 1}
    untouched = [i for i in range(n) if i not in touched]
    assert untouched
    np.testing.assert_array_equal(np.asarray(new_tab)[untouched],
                                  np.asarray(table)[untouched])


def test_memory_update_table_matches_unfused_chain():
    """The fused table kernel must equal gather -> memory_update kernel ->
    scatter — the three dispatches it collapses."""
    rng = np.random.default_rng(56)
    n, m, d = 80, 50, 32
    args = _memory_update_table_args(rng, n, m, d)
    (table, last_t, x, gidx, widx, times, w, u, b, dm, scale, gamma) = args
    new_tab, new_lt, s_meas, fused, delta = ops.memory_update_table(
        *args, interpret=True, clip=1.0)
    tab_pad = jnp.concatenate([table, jnp.zeros((2, d), table.dtype)])
    lt_pad = jnp.concatenate([last_t, jnp.zeros((2,), last_t.dtype)])
    h = tab_pad[gidx]
    s2, f2, d2 = ops.memory_update(x, h, w, u, b, dm, scale, gamma,
                                   interpret=True, clip=1.0)
    np.testing.assert_allclose(np.asarray(s_meas), np.asarray(s2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(f2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(delta), np.asarray(d2), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(new_tab), np.asarray(tab_pad.at[widx].set(f2)[:n]),
        atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(new_lt), np.asarray(lt_pad.at[widx].set(times)[:n]),
        atol=1e-5)


@pytest.mark.parametrize("delta_mode", ["innovation", "transition"])
def test_memory_update_table_gradients_match_oracle(delta_mode):
    """Custom VJP vs jax.grad of the ref over every float input — the table
    cotangent must flow through the gather/scatter transposes."""
    rng = np.random.default_rng(57)
    args = _memory_update_table_args(rng, 40, 30, 16)
    # differentiable args: everything except the int32 index operands (3, 4)
    argnums = (0, 1, 2, 5, 6, 7, 8, 9, 10, 11)

    def loss(fn):
        def f(*a):
            new_tab, new_lt, s_meas, fused, delta = fn(*a, clip=1.0,
                                                       delta_mode=delta_mode)
            return (jnp.sum(new_tab ** 2) + jnp.sum(new_lt ** 2)
                    + jnp.sum(s_meas ** 2) + jnp.sum(fused ** 2)
                    + jnp.sum(delta ** 2))
        return f

    import functools
    gk = jax.grad(loss(functools.partial(ops.memory_update_table,
                                         interpret=True)),
                  argnums=argnums)(*args)
    gr = jax.grad(loss(ref.memory_update_table_ref), argnums=argnums)(*args)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _split_rnn_path(args, clip, delta_mode):
    """The unfused jnp chain the RNN form replaces: gather the previous
    rows (masked occurrences read zeros), the program's RNN cell
    (models/modules.py::rnn_cell), PRES predict -> correct -> delta rate,
    and the last-occurrence scatter (models/mdgnn.py::scatter_rows)."""
    from repro.models import mdgnn, modules
    (table, last_t, x, gidx, widx, times, w, u, b, dm, scale, gamma) = args
    n = table.shape[0]
    h = jnp.where((gidx < n)[:, None], table[jnp.minimum(gidx, n - 1)], 0.0)
    s_meas = modules.rnn_cell({"w": w, "u": u, "b": b}, x, h)
    s_pred = h + jnp.clip(scale[:, None] * dm, -clip, clip)
    fused = (1.0 - gamma) * s_pred + gamma * s_meas
    base = s_pred if delta_mode == "innovation" else h
    delta = (fused - base) / jnp.maximum(scale, 1.0)[:, None]
    return (mdgnn.scatter_rows(table, widx, fused),
            mdgnn.scatter_rows(last_t, widx, times), s_meas, fused, delta)


@pytest.mark.parametrize("n,m", [(20, 1), (50, 64), (300, 200)])
@pytest.mark.parametrize("delta_mode", ["innovation", "transition"])
def test_memory_update_table_rnn_matches_split_path(n, m, delta_mode):
    """The RNN form (interpret mode) against the split jnp path, with
    nodes repeated within the batch (n=50 < m=64 forces repeats) and
    masked occurrences (a fifth of them)."""
    rng = np.random.default_rng(7 * n + m)
    args = _memory_update_table_args(rng, n, m, 32, gates=1)
    widx = np.asarray(args[4])
    if m > n:
        assert len(np.unique(np.asarray(args[3])[widx < n])) < int(m * 0.8)
    got = ops.memory_update_table(*args, interpret=True, clip=1.0,
                                  delta_mode=delta_mode, cell="rnn")
    want = _split_rnn_path(args, 1.0, delta_mode)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)
    oracle = ref.memory_update_table_ref(*args, clip=1.0,
                                         delta_mode=delta_mode, cell="rnn")
    for g, w in zip(got, oracle):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_memory_update_table_rnn_gradients_match_oracle():
    """The RNN form's custom VJP vs jax.grad of its oracle."""
    import functools
    rng = np.random.default_rng(58)
    args = _memory_update_table_args(rng, 40, 30, 16, gates=1)
    argnums = (0, 1, 2, 5, 6, 7, 8, 9, 10, 11)

    def loss(fn):
        def f(*a):
            outs = fn(*a, clip=1.0, delta_mode="transition", cell="rnn")
            return sum(jnp.sum(o ** 2) for o in outs)
        return f

    gk = jax.grad(loss(functools.partial(ops.memory_update_table,
                                         interpret=True)),
                  argnums=argnums)(*args)
    gr = jax.grad(loss(ref.memory_update_table_ref), argnums=argnums)(*args)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("cell,logged", [("gru", "memory_update_table"),
                                         ("rnn", "memory_update_table_rnn")])
def test_memory_update_table_form_in_dispatch_log(cell, logged):
    """The dispatch log keys each form of the table kernel by the name its
    pallas_call carries; an unknown cell is refused."""
    from repro.kernels import memory_update as mu
    rng = np.random.default_rng(59)
    args = _memory_update_table_args(rng, 20, 16, 8,
                                     gates=mu.GATES[cell])
    ops.reset_dispatch_log()
    ops.memory_update_table(*args, mode="oracle", cell=cell)
    assert ops.dispatch_log() == {logged: {"oracle": 1}}
    assert mu.table_kernel_name(cell) == logged
    with pytest.raises(ValueError, match="memory cell"):
        mu.table_kernel_name("lstm")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_entries_complete():
    """Every kernel has a Pallas impl, a ref oracle (the parity target) and
    a one-line doc; dispatch resolves by name."""
    expected = {"gru_cell", "pres_filter", "pres_predict", "memory_update",
                "memory_update_table", "link_score", "neighbor_attn",
                "embed_attn", "ssd_chunk", "flash_attn"}
    assert expected == set(ops.REGISTRY)
    for name, spec in ops.REGISTRY.items():
        assert spec.name == name
        assert callable(spec.impl) and callable(spec.ref)
        assert spec.doc
    with pytest.raises(KeyError, match="unknown kernel"):
        ops.get_kernel("nope")


def test_registry_dispatch_equals_wrapper():
    rng = np.random.default_rng(5)
    d = 32
    x = jnp.asarray(rng.normal(size=(17, d)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(17, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, 3 * d)) * 0.1, jnp.float32)
    u = jnp.asarray(rng.normal(size=(d, 3 * d)) * 0.1, jnp.float32)
    b = jnp.zeros((3 * d,), jnp.float32)
    got = ops.dispatch("gru_cell", x, h, w, u, b, interpret=True)
    want = ops.gru_cell(x, h, w, u, b, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# link_score (serving recommend-topk scoring, docs/SERVING.md)
# ---------------------------------------------------------------------------


def _link_score_inputs(b, i, d, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(i, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(2 * d, d)) * 0.2, jnp.float32),
            jnp.asarray(rng.normal(size=(d,)) * 0.1, jnp.float32),
            jnp.asarray(rng.normal(size=(d, 1)) * 0.2, jnp.float32),
            jnp.asarray(rng.normal(size=(1,)) * 0.1, jnp.float32))


@pytest.mark.parametrize("b,i,d", [(1, 5, 32), (7, 30, 16), (40, 200, 32)])
def test_link_score_matches_ref(b, i, d):
    args = _link_score_inputs(b, i, d, seed=b * 100 + i)
    out = ops.link_score(*args, interpret=True)
    want = ref.link_score_ref(*args)
    assert out.shape == (b, i)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_link_score_matches_pairwise_decoder():
    """Row (b, i) must equal mdgnn.link_logits on that single pair — the
    factored pairwise kernel and the training decoder are the same math."""
    from repro.models import mdgnn
    h_src, h_items, w1, b1, w2, b2 = _link_score_inputs(4, 9, 16, seed=3)
    params = {"dec": {"w1": w1, "b1": b1, "w2": w2, "b2": b2}}
    got = ops.link_score(h_src, h_items, w1, b1, w2, b2, interpret=True)
    for bi in range(4):
        row = mdgnn.link_logits(
            params, jnp.broadcast_to(h_src[bi], h_items.shape), h_items)
        np.testing.assert_allclose(np.asarray(got[bi]), np.asarray(row),
                                   atol=1e-5, rtol=1e-5)


def test_link_score_gradients_match_oracle():
    args = _link_score_inputs(6, 20, 16, seed=7)

    def loss_k(*a):
        return jnp.sum(jnp.tanh(ops.link_score(*a, interpret=True)))

    def loss_r(*a):
        return jnp.sum(jnp.tanh(ref.link_score_ref(*a)))

    gk = jax.grad(loss_k, argnums=tuple(range(6)))(*args)
    gr = jax.grad(loss_r, argnums=tuple(range(6)))(*args)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# neighbor_attn
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,e", [(1, 4, 32), (64, 16, 128), (130, 10, 64)])
def test_neighbor_attn_matches_ref(m, k, e):
    rng = np.random.default_rng(m + k + e)
    q = jnp.asarray(rng.normal(size=(m, e)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(m, k, e)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(m, k, e)), jnp.float32)
    valid = jnp.asarray(rng.random((m, k)) > 0.3)
    got = ops.neighbor_attn(q, kk, v, valid, interpret=True)
    want = ref.neighbor_attn_ref(q, kk, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_neighbor_attn_all_invalid_rows():
    """A node with zero valid neighbours must produce zeros, not NaNs."""
    rng = np.random.default_rng(4)
    m, k, e = 8, 6, 32
    q = jnp.asarray(rng.normal(size=(m, e)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(m, k, e)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(m, k, e)), jnp.float32)
    valid = jnp.zeros((m, k), bool)
    got = ops.neighbor_attn(q, kk, v, valid, interpret=True)
    want = ref.neighbor_attn_ref(q, kk, v, valid)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# embed_attn
# ---------------------------------------------------------------------------


def _embed_attn_args(r, k, u, seed=0, d_self=8, d_tab=8, d_time=4, e=8):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(r, d_self)), jnp.float32),
            jnp.asarray(rng.normal(size=(u, d_tab)), jnp.float32),
            jnp.asarray(rng.integers(0, u, size=(r, k)), jnp.int32),
            jnp.asarray(rng.normal(size=(r, k)), jnp.float32),
            jnp.asarray(rng.random((r, k)) > 0.3),
            jnp.asarray(rng.normal(size=(d_time,)), jnp.float32),
            jnp.asarray(rng.normal(size=(d_time,)), jnp.float32),
            jnp.asarray(rng.normal(size=(d_self, e)), jnp.float32),
            jnp.asarray(rng.normal(size=(d_tab + d_time, e)), jnp.float32),
            jnp.asarray(rng.normal(size=(d_tab + d_time, e)), jnp.float32))


@pytest.mark.parametrize("r,k,h,bk", [(8, 4, 1, 8), (16, 4, 2, 8),
                                      (13, 5, 2, 8),  # R % block_r != 0
                                      (2, 3, 1, 16)])  # block_r > R
def test_embed_attn_matches_ref(r, k, h, bk):
    """Interpret-mode Pallas (DMA row gather + online softmax) against the
    pure-jnp oracle, including padded parent-row blocks."""
    args = _embed_attn_args(r, k, u=r + 3, seed=r * k + h)
    got = ops.embed_attn(*args, n_heads=h, block_r=bk, interpret=True)
    want = ref.embed_attn_ref(*args, n_heads=h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_embed_attn_all_invalid_rows():
    """A parent with zero valid neighbours must produce zeros, not NaNs
    (the online-softmax accumulator never sees a live slot)."""
    args = list(_embed_attn_args(5, 4, u=6, seed=3))
    args[4] = jnp.zeros((5, 4), bool)
    got = ops.embed_attn(*args, n_heads=2, block_r=8, interpret=True)
    want = ref.embed_attn_ref(*args, n_heads=2)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_embed_attn_grads_match_oracle():
    """The custom VJP (Pallas forward, oracle backward) must agree with
    grad-of-oracle on every differentiable input — notably the table,
    whose cotangent flows through the gather transpose (a scatter-add)."""
    args = _embed_attn_args(4, 4, u=7, seed=9)
    argnums = (0, 1, 7, 8, 9)   # h_self, tab, wq, wk, wv

    def loss(fn, extra):
        return lambda *diff: jnp.sum(
            fn(*(list(diff[:2]) + list(args[2:7]) + list(diff[2:])),
               **extra) ** 2)

    diff_args = tuple(args[i] for i in argnums)
    gk = jax.grad(loss(ops.embed_attn,
                       dict(n_heads=2, block_r=8, interpret=True)),
                  argnums=tuple(range(5)))(*diff_args)
    gr = jax.grad(loss(ref.embed_attn_ref, dict(n_heads=2)),
                  argnums=tuple(range(5)))(*diff_args)
    # relative to each gradient's scale: the entries reach ~80, where float32
    # summation-order differences alone are ~1e-5
    for a, b in zip(gk, gr):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                                   atol=5e-6 * np.abs(b).max())


# ---------------------------------------------------------------------------
# ssd_chunk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("g,l,n,p", [(1, 64, 32, 32), (4, 128, 64, 64),
                                     (2, 256, 128, 128)])
def test_ssd_chunk_matches_ref(g, l, n, p):
    rng = np.random.default_rng(g * l)
    q = jnp.asarray(rng.normal(size=(g, l, n)) * 0.1, jnp.float32)
    k = jnp.asarray(rng.normal(size=(g, l, n)) * 0.1, jnp.float32)
    v = jnp.asarray(rng.normal(size=(g, l, p)) * 0.1, jnp.float32)
    lcum = jnp.cumsum(
        jnp.asarray(-np.abs(rng.normal(size=(g, l)) * 0.05), jnp.float32), -1)
    h0 = jnp.asarray(rng.normal(size=(g, n, p)) * 0.1, jnp.float32)
    y_k, h_k = ops.ssd_chunk(q, k, v, lcum, h0, interpret=True)
    y_r, h_r = jax.vmap(ref.ssd_chunk_ref)(q, k, v, lcum, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-5)


def test_ssd_chunking_is_exact():
    """Two chained chunks == one double-length chunk (the inter-chunk scan
    carries exactly the right state)."""
    rng = np.random.default_rng(12)
    l, n, p = 64, 32, 32
    q = jnp.asarray(rng.normal(size=(2 * l, n)) * 0.1, jnp.float32)
    k = jnp.asarray(rng.normal(size=(2 * l, n)) * 0.1, jnp.float32)
    v = jnp.asarray(rng.normal(size=(2 * l, p)) * 0.1, jnp.float32)
    logd = jnp.asarray(-np.abs(rng.normal(size=(2 * l,)) * 0.05), jnp.float32)
    h0 = jnp.zeros((n, p), jnp.float32)
    # full
    y_full, h_full = ref.ssd_chunk_ref(q, k, v, jnp.cumsum(logd), h0)
    # chunked
    y1, h_mid = ref.ssd_chunk_ref(q[:l], k[:l], v[:l], jnp.cumsum(logd[:l]), h0)
    y2, h_end = ref.ssd_chunk_ref(q[l:], k[l:], v[l:], jnp.cumsum(logd[l:]),
                                  h_mid)
    np.testing.assert_allclose(np.asarray(y_full[:l]), np.asarray(y1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_full[l:]), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h_end), atol=1e-4)


# ---------------------------------------------------------------------------
# flash_attn
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
@pytest.mark.parametrize("g,s,d,qb,kb", [(2, 256, 64, 64, 64),
                                         (1, 512, 128, 128, 64)])
def test_flash_attn_matches_ref(causal, window, g, s, d, qb, kb):
    from repro.kernels import flash_attn as FA
    rng = np.random.default_rng(g * s + d)
    q = jnp.asarray(rng.normal(size=(g, s, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(g, s, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(g, s, d)) * 0.3, jnp.float32)
    got = ops.flash_attn(q, k, v, causal=causal, window=window,
                         q_block=qb, kv_block=kb, interpret=True)
    want = FA.flash_attn_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_flash_attn_gqa_kv_sharing():
    """GQA: kv heads indexed by query_head // n_rep inside the BlockSpec."""
    from repro.kernels import flash_attn as FA
    rng = np.random.default_rng(11)
    b, hq, hkv, s, d = 2, 8, 2, 128, 32
    q = jnp.asarray(rng.normal(size=(b * hq, s, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(b * hkv, s, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(b * hkv, s, d)) * 0.3, jnp.float32)
    got = ops.flash_attn(q, k, v, q_block=64, kv_block=64, interpret=True)
    want = FA.flash_attn_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_flash_attn_bf16_io():
    from repro.kernels import flash_attn as FA
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 128, 64)) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 128, 64)) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 128, 64)) * 0.3, jnp.bfloat16)
    got = ops.flash_attn(q, k, v, q_block=64, kv_block=64, interpret=True)
    want = FA.flash_attn_ref(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_flash_attn_gradients_match_oracle():
    from repro.kernels import flash_attn as FA
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(1, 128, 32)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 32)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 128, 32)) * 0.3, jnp.float32)
    gk = jax.grad(lambda *a: jnp.sum(ops.flash_attn(
        *a, q_block=64, kv_block=64, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(FA.flash_attn_ref(*a) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


# ---------------------------------------------------------------------------
# Gradients: every kernel's custom_vjp must match the oracle's gradient
# ---------------------------------------------------------------------------


def test_gru_cell_gradients_match_oracle():
    rng = np.random.default_rng(21)
    b, d = 64, 64
    args = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
            for s in [(b, d), (b, d), (d, 3 * d), (d, 3 * d), (3 * d,)]]
    g_kernel = jax.grad(lambda *a: jnp.sum(ops.gru_cell(*a,
                                                        interpret=True) ** 2),
                        argnums=(0, 1, 2, 3, 4))(*args)
    g_ref = jax.grad(lambda *a: jnp.sum(ref.gru_cell_ref(*a) ** 2),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for gk, gr in zip(g_kernel, g_ref):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-4)


def test_pres_filter_gradient_flows_to_gamma():
    """gamma is the learnable Eq. 8 gate — its gradient must be non-zero."""
    rng = np.random.default_rng(22)
    n, d = 32, 16
    s_prev = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    s_meas = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    dm = jnp.asarray(rng.normal(size=(n, d)) * 0.01, jnp.float32)
    dt = jnp.ones((n,), jnp.float32)

    def loss(gamma):
        fused, _ = ops.pres_filter(s_prev, s_meas, dm, dt, gamma,
                                   interpret=True)
        return jnp.sum(fused ** 2)

    g = jax.grad(loss)(jnp.asarray(0.5, jnp.float32))
    g_ref = jax.grad(lambda gm: jnp.sum(
        ref.pres_filter_ref(s_prev, s_meas, dm, dt, gm)[0] ** 2))(
            jnp.asarray(0.5, jnp.float32))
    assert abs(float(g)) > 0
    np.testing.assert_allclose(float(g), float(g_ref), rtol=1e-4)


def test_neighbor_attn_gradients_match_oracle():
    rng = np.random.default_rng(23)
    m, k, e = 32, 8, 32
    q = jnp.asarray(rng.normal(size=(m, e)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(m, k, e)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(m, k, e)), jnp.float32)
    valid = jnp.asarray(rng.random((m, k)) > 0.3)
    gk = jax.grad(lambda a, b, c: jnp.sum(
        ops.neighbor_attn(a, b, c, valid, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, kk, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        ref.neighbor_attn_ref(a, b, c, valid) ** 2), argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("delta_mode", ["innovation", "transition"])
def test_memory_update_gradients_match_oracle(delta_mode):
    """The fused kernel's custom VJP vs jax.grad of the composed oracle,
    over every differentiable input."""
    rng = np.random.default_rng(42)
    args = _memory_update_args(rng, 96, 32)
    argnums = tuple(range(len(args)))

    def loss_k(*a):
        s_meas, fused, delta = ops.memory_update(*a, interpret=True,
                                                 delta_mode=delta_mode)
        return jnp.sum(fused ** 2) + jnp.sum(delta ** 2) + jnp.sum(s_meas ** 2)

    def loss_r(*a):
        s_meas, fused, delta = ref.memory_update_ref(*a,
                                                     delta_mode=delta_mode)
        return jnp.sum(fused ** 2) + jnp.sum(delta ** 2) + jnp.sum(s_meas ** 2)

    gk = jax.grad(loss_k, argnums=argnums)(*args)
    gr = jax.grad(loss_r, argnums=argnums)(*args)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_memory_update_gamma_gradient_flows():
    """gamma is the learnable Eq. 8 gate — the fused kernel must pass its
    gradient through (it is how the filter learns how much to trust the
    measurement)."""
    rng = np.random.default_rng(43)
    args = _memory_update_args(rng, 64, 16)

    def loss(gamma):
        _, fused, _ = ops.memory_update(*args[:-1], gamma, interpret=True)
        return jnp.sum(fused ** 2)

    g = jax.grad(loss)(jnp.asarray(0.5, jnp.float32))
    g_ref = jax.grad(lambda gm: jnp.sum(
        ref.memory_update_ref(*args[:-1], gm)[1] ** 2))(
            jnp.asarray(0.5, jnp.float32))
    assert abs(float(g)) > 0
    np.testing.assert_allclose(float(g), float(g_ref), rtol=1e-4)


def test_ssd_chunk_gradients_match_oracle():
    rng = np.random.default_rng(24)
    g_, l, n, p = 2, 64, 32, 32
    q = jnp.asarray(rng.normal(size=(g_, l, n)) * 0.1, jnp.float32)
    k = jnp.asarray(rng.normal(size=(g_, l, n)) * 0.1, jnp.float32)
    v = jnp.asarray(rng.normal(size=(g_, l, p)) * 0.1, jnp.float32)
    lcum = jnp.cumsum(
        jnp.asarray(-np.abs(rng.normal(size=(g_, l)) * 0.05), jnp.float32), -1)
    h0 = jnp.asarray(rng.normal(size=(g_, n, p)) * 0.1, jnp.float32)

    def loss_k(*a):
        y, h1 = ops.ssd_chunk(*a, interpret=True)
        return jnp.sum(y ** 2) + jnp.sum(h1 ** 2)

    def loss_r(*a):
        y, h1 = jax.vmap(ref.ssd_chunk_ref)(*a)
        return jnp.sum(y ** 2) + jnp.sum(h1 ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3, 4))(q, k, v, lcum, h0)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3, 4))(q, k, v, lcum, h0)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
