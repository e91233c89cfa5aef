"""Compile the main path's Pallas kernels for one TPU v5e chip, without one.

The TPU compiler (Mosaic) ships with jaxlib and compiles for a chip that
is described, not attached. Interpret-mode parity (tests/test_kernels.py)
cannot show what Mosaic refuses — unaligned block shapes, DMA slices that
split a tile, layouts it cannot map — so every kernel the training and
serving path dispatches is compiled here at the widths of the paper's
model (`configs/tgn_pres.py::CONFIG` on the JODIE-Wikipedia node table)
and of `PRODUCTION`, and the compiled text must hold the kernel
(`tpu_custom_call`), named after the kernel's registry name (the fused
table kernel's RNN form after its form, `memory_update_table_rnn`). Nothing
runs, so this says nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.tgn_pres import CONFIG, PRODUCTION
from repro.kernels import embed_attn as ea
from repro.kernels import gru_cell as gc
from repro.kernels import link_score as ls
from repro.kernels import memory_update as mu
from repro.kernels import pres_filter as pf

WIDTHS = {
    # JODIE-Wikipedia: 8,227 users + 1,000 items
    "config": dataclasses.replace(CONFIG, n_nodes=9227, d_edge=172),
    "production": PRODUCTION,
}
ROWS = 400      # touched occurrences / frontier rows per call
ITEMS = 1000    # candidate items scored per top-k request


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _case(kernel: str, cfg, spec):
    """(compiled-mode Pallas entry, argument shapes) at cfg's widths."""
    f32, i32 = jnp.float32, jnp.int32
    n, d, din = cfg.n_nodes, cfg.d_mem, cfg.d_msg
    dt, kk, e, m = cfg.d_time, cfg.n_neighbors, cfg.d_embed, ROWS
    if kernel.startswith("memory_update_table"):
        # the GRU form, or the RNN form (`memory_update_table_rnn`)
        cell = kernel.rpartition("_")[2] if kernel.endswith("_rnn") \
            else "gru"
        gd = mu.GATES[cell] * d
        return functools.partial(mu._memory_update_table_pallas,
                                 cell=cell), (
            spec((n, d)), spec((n,)), spec((m, din)), spec((m,), i32),
            spec((m,), i32), spec((m,)), spec((din, gd)),
            spec((d, gd)), spec((gd,)), spec((m, d)), spec((m,)),
            spec(()))
    if kernel == "embed_attn":
        return (lambda *a, interpret: ea._embed_attn_pallas(
            *a, n_heads=cfg.n_heads, interpret=interpret)), (
            spec((m, d)), spec((m, d)), spec((m, kk), i32), spec((m, kk)),
            spec((m, kk), jnp.bool_), spec((dt,)), spec((dt,)),
            spec((d, e)), spec((d + dt, e)), spec((d + dt, e)))
    if kernel == "pres_filter":
        return pf._pres_filter_pallas, (
            spec((m, d)), spec((m, d)), spec((m, d)), spec((m,)), spec(()))
    if kernel == "pres_predict":
        return mu._pres_predict_pallas, (spec((n, d)), spec((n, d)),
                                         spec((n,)))
    if kernel == "gru_cell":
        return gc._gru_cell_pallas, (spec((m, din)), spec((m, d)),
                                     spec((din, 3 * d)), spec((d, 3 * d)),
                                     spec((3 * d,)))
    if kernel == "link_score":
        return ls._link_score_pallas, (
            spec((64, e)), spec((ITEMS, e)), spec((2 * e, e)), spec((e,)),
            spec((e, 1)), spec((1,)))
    raise KeyError(kernel)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["memory_update_table", "embed_attn",
                                    "pres_filter", "pres_predict",
                                    "gru_cell", "link_score",
                                    "memory_update_table_rnn"])
def test_kernel_compiles_for_v5e(one_chip, kernel, widths):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _case(kernel, WIDTHS[widths], spec)
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's custom call is named after its registry name, so a
    # profiler trace's op events find it by that name
    from bench.lib import tracereduce
    calls = [{"name": m.group(1), "dur_ns": 1.0}
             for m in _CUSTOM_CALL.finditer(text)]
    assert calls and all(re.fullmatch(re.escape(kernel) + r"(\.\d+)?",
                                      c["name"]) for c in calls)
    assert tracereduce.kernel_ns(calls, kernel) == (len(calls), len(calls))


_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([^ =]+) = .*"
                          r'custom_call_target="tpu_custom_call"', re.M)
