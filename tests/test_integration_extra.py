"""Extra integration coverage: the Def. 3 coherence probe inside real
training, long-context decode for the sub-quadratic archs, and the 5th
(GDELT-like) dataset."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.graph import datasets


def test_gdelt_like_generator():
    stream = datasets.get_dataset("gdelt-small")
    assert len(stream) == 40_000
    assert stream.feat.shape[1] == 24
    assert np.all(np.diff(stream.t) >= 0)


def test_empirical_coherence_during_training():
    """Def. 3's mu-hat must be computable mid-training at O(|B|) cost:
    gradients of the decoder loss w.r.t. stale vs fresh endpoint memory."""
    from repro.core import coherence
    from repro.models import mdgnn
    from repro.models.mdgnn import MDGNNConfig

    spec = datasets.SyntheticSpec("muhat", 40, 30, 500, 4)
    stream = datasets.generate(spec, seed=0)
    cfg = MDGNNConfig(variant="jodie", n_nodes=stream.num_nodes, d_edge=4,
                      d_mem=8, d_msg=8, d_time=4, d_embed=8)
    params, _ = mdgnn.init_params(jax.random.PRNGKey(0), cfg)
    state = mdgnn.init_state(cfg)
    batches = stream.temporal_batches(100)
    # stale memory = before batch 0; fresh = after batch 0
    mem_stale = state["memory"]
    mem_fresh, _ = mdgnn.memory_update(params, cfg, mem_stale, batches[0])
    ev = batches[1]

    def loss_at(params, mem_rows):
        """decoder loss of batch-1 events at the given endpoint rows."""
        e = params["emb"]["l0"]   # jodie_proj params (registry layout)
        # the time projection at zero elapsed time: h = s . (1 + b)
        h = mem_rows * (1.0 + e["b"])
        hs, hd = h[: ev.size], h[ev.size:]
        logits = mdgnn.link_logits(params, hs, hd)
        return jnp.mean(jax.nn.softplus(-logits))

    rows_stale = jnp.concatenate([mem_stale.mem[ev.src],
                                  mem_stale.mem[ev.dst]])
    rows_fresh = jnp.concatenate([mem_fresh.mem[ev.src],
                                  mem_fresh.mem[ev.dst]])
    mu = coherence.empirical_memory_coherence(loss_at, params,
                                              rows_stale, rows_fresh)
    assert np.isfinite(float(mu))


@pytest.mark.parametrize("arch_id", ["xlstm-350m", "zamba2-1.2b"])
def test_long_context_decode_state_is_bounded(arch_id):
    """long_500k archs: decode state size must be independent of the
    context length (O(1) recurrent state)."""
    from repro.archs.api import get_model

    cfg = get_config(arch_id).reduced()
    model = get_model(cfg)
    small = model.init_decode_state(1, 128)
    large = model.init_decode_state(1, 4096)
    bytes_of = lambda st: sum(l.size * l.dtype.itemsize
                              for l in jax.tree.leaves(st))
    # hybrid zamba has attention caches too; the SSM portion dominates and
    # xlstm is strictly O(1)
    if arch_id == "xlstm-350m":
        assert bytes_of(small) == bytes_of(large)
    else:
        assert bytes_of(large) < bytes_of(small) * 40


def test_gemma_long_context_cache_is_mostly_bounded():
    """gemma3: 5 of 6 layers have window-bounded ring caches; only the
    global layers scale with context."""
    from repro.archs.api import get_model

    cfg = get_config("gemma3-12b").reduced()
    assert cfg.window
    model = get_model(cfg)
    st1 = model.init_decode_state(1, cfg.window * 4)
    st2 = model.init_decode_state(1, cfg.window * 16)
    bytes_of = lambda st: sum(l.size * l.dtype.itemsize
                              for l in jax.tree.leaves(st))
    # local caches bounded at `window`; growth only from global layers
    assert bytes_of(st2) < bytes_of(st1) * 16


def test_serve_zoo_driver_all_families():
    """The serving CLI's zoo loop must run for a dense, an enc-dec and an
    SSM arch (covers the encoder-prefill special case)."""
    from repro.launch import serve

    for arch in ("qwen3-0.6b", "whisper-tiny", "xlstm-350m"):
        serve.serve_zoo(arch, steps=2)


def test_decode_beyond_32k_positions():
    """decode_step at a position far beyond training length must stay
    finite (RoPE extrapolation, ring-buffer windows)."""
    from repro.archs.api import get_model

    cfg = get_config("qwen3-0.6b").reduced()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    state = model.init_decode_state(1, 64)
    logits, _ = model.decode_step(params, state, jnp.ones((1, 1), jnp.int32),
                                  jnp.asarray(50_000))
    assert bool(jnp.all(jnp.isfinite(logits)))
