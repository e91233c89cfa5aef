"""Seeded event streams and arrival clocks.

Copied from the program so that a later change to it cannot move the
yardstick:

* `stream` follows `src/repro/graph/datasets.py::stream_chunk` (the
  stateless, vectorised power-law bipartite stream). One change: the edge
  features come from one seeded `numpy` generator call instead of one hash
  stream per column, which caps `stream_chunk` at 60 columns and would be
  too slow at 172.
* `poisson_arrival_clock` and `late_arrival_order` are
  `src/repro/graph/events.py`'s, unchanged.
"""
from __future__ import annotations

import numpy as np

_SM_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_SM_MUL1 = np.uint64(0xBF58476D1CE4E5B9)
_SM_MUL2 = np.uint64(0x94D049BB133111EB)
_N_STREAMS = 64


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = (x + _SM_GAMMA).astype(np.uint64)
        z = ((z ^ (z >> np.uint64(30))) * _SM_MUL1).astype(np.uint64)
        z = ((z ^ (z >> np.uint64(27))) * _SM_MUL2).astype(np.uint64)
        return z ^ (z >> np.uint64(31))


def _u01(seed: int, idx: np.ndarray, stream: int) -> np.ndarray:
    """Uniforms in [0, 1), one per (event, stream), from (seed, index)."""
    with np.errstate(over="ignore"):
        key = _splitmix64(np.uint64(seed % (1 << 64)) * np.uint64(_N_STREAMS + 1)
                          + np.uint64(stream))
        h = _splitmix64(idx.astype(np.uint64) * np.uint64(_N_STREAMS)
                        + np.uint64(stream) + key)
    return (h >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))


def _power_rank(u: np.ndarray, n: int, exponent: float) -> np.ndarray:
    """Bounded power-law rank in [0, n) by inverse CDF, density
    proportional to (rank + 1) ** -exponent."""
    if exponent <= 1.0:
        raise ValueError(f"power-law exponent must be > 1, got {exponent}")
    one_minus_a = 1.0 - exponent
    hi = float(n + 1) ** one_minus_a
    x = (1.0 + u * (hi - 1.0)) ** (1.0 / one_minus_a)
    return np.minimum(x.astype(np.int64) - 1, n - 1)


def stream(n_users: int, n_items: int, n_events: int, feat_dim: int,
           seed: int, exponent: float = 1.6, noise: float = 0.1,
           dt: float = 1.0):
    """The whole stream as (src, dst, t, feat) numpy arrays: users are ids
    [0, n_users), items [n_users, n_users + n_items). Each user prefers a
    rotation of the global item ranking; `noise` of the events pick a
    uniform item; timestamps are `(i + u_i) * dt` in float32."""
    idx = np.arange(n_events, dtype=np.uint64)
    users = _power_rank(_u01(seed, idx, 0), n_users, exponent)
    base = _power_rank(_u01(seed, idx, 1), n_items, exponent)
    with np.errstate(over="ignore"):
        offset = (_splitmix64(users.astype(np.uint64)
                              + np.uint64(seed % (1 << 64)))
                  % np.uint64(n_items)).astype(np.int64)
    items = (base + offset) % n_items
    uniform = np.minimum((_u01(seed, idx, 2) * n_items).astype(np.int64),
                         n_items - 1)
    items = np.where(_u01(seed, idx, 3) < noise, uniform, items)
    t = ((idx.astype(np.float64) + _u01(seed, idx, 4)) * dt).astype(np.float32)
    rng = np.random.default_rng([seed, 1])
    feat = rng.random((n_events, feat_dim), dtype=np.float32)
    feat *= np.float32(0.2)
    feat -= np.float32(0.1)
    feat[np.arange(n_events), users % feat_dim] += np.float32(1.0)
    return (users.astype(np.int32), (n_users + items).astype(np.int32), t,
            feat)


def poisson_arrival_clock(n: int, rate: float, seed: int = 0) -> np.ndarray:
    """Wall-clock arrival times of `n` events, a Poisson process of `rate`
    events per second."""
    if rate <= 0:
        raise ValueError(f"arrival rate must be > 0 events/sec, got {rate}")
    rng = np.random.default_rng(seed)
    return rng.exponential(1.0 / rate, n).cumsum()


def late_arrival_order(n: int, frac: float, max_late: int,
                       seed: int = 0) -> np.ndarray:
    """Delivery order in which a `frac` subset of events is delayed by up
    to `max_late` positions."""
    if not 0.0 <= frac <= 1.0:
        raise ValueError(f"late fraction must be in [0, 1], got {frac}")
    if max_late < 0:
        raise ValueError(f"max_late must be >= 0, got {max_late}")
    keys = np.arange(n, dtype=np.float64)
    if frac > 0.0 and max_late > 0:
        rng = np.random.default_rng(seed)
        late = rng.random(n) < frac
        keys[late] += rng.integers(1, max_late + 1, int(late.sum())) + 0.5
    return np.argsort(keys, kind="stable")
