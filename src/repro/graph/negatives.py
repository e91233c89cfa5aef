"""Negative event sampling (Assumption 1: unbiased, bounded variance).

For each positive batch B_i we draw the negative set \bar B_i by corrupting
destinations uniformly from the destination-node range — the standard MDGNN
protocol (Rossi et al., 2021; Zhou et al., 2022)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.graph.events import EventBatch


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NegativeDraw:
    """What a train or eval step needs to draw its own negatives: the PRNG
    key before this step's split and the destination bounds [lo, hi) as a
    device int32 pair. Passed in a step's negatives slot instead of an
    `EventBatch`, it moves the split and the sampling inside the compiled
    step; the step hands back the next key."""
    key: jax.Array        # PRNG key, split once per step
    dst: jax.Array        # (2,) int32 — [dst_lo, dst_hi)

    @staticmethod
    def start(key, dst_range) -> "NegativeDraw":
        """An epoch's first draw: the bounds go to the device once here."""
        return NegativeDraw(key, jnp.asarray(dst_range, jnp.int32))


def sample_negatives_in(key, batch: EventBatch, dst_lo, dst_hi,
                        num: int | None = None) -> EventBatch:
    """In-step (jit/scan-safe) negative sampling.

    Every op here is traceable, so the sequential and scan-compiled steps
    run it INSIDE the compiled step, driven by a PRNG key carried from step
    to step — no host-side key split or device transfer per temporal batch.
    `num` must be static under jit (shapes); the dst bounds may be python
    ints or traced scalars."""
    n = num or batch.size
    idx = jax.random.randint(key, (n,), 0, batch.size)
    neg_dst = jax.random.randint(key, (n,), dst_lo, dst_hi)
    return EventBatch(
        src=batch.src[idx],
        dst=neg_dst.astype(jnp.int32),
        t=batch.t[idx],
        feat=jnp.zeros((n, batch.feat.shape[1]), batch.feat.dtype),
        mask=batch.mask[idx],
    )


def split_and_sample(key, batch: EventBatch, dst_lo, dst_hi):
    """One step's negatives in the order every engine uses: split the
    carried key (`key, sub = split(key)`), sample with `sub`. Traceable.
    Returns (negatives, next key)."""
    key, sub = jax.random.split(key)
    return sample_negatives_in(sub, batch, dst_lo, dst_hi), key


def sample_negatives(key, batch: EventBatch, dst_lo: int, dst_hi: int,
                     num: int | None = None) -> EventBatch:
    """Host-loop entry point; identical sampling to `sample_negatives_in`
    (the in-step draw must reproduce host-sampled negatives bit for bit)."""
    return sample_negatives_in(key, batch, dst_lo, dst_hi, num=num)
