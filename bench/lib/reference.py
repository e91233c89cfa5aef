"""Plain reference of MDGNN-PRES training and serving, in `jax.numpy`.

Written from the papers and the configuration, not from the program: it
imports nothing of `src/` and takes nothing the program made. The
benchmark makes the weights (`init_params`) and the events, hands them to
the program and to this reference alike, and compares what comes out
(`bench/lib/compare.py`).

This module holds what every MDGNN shares. What differs from one to
another (the embedding's weights and forward pass, tables the model adds
to the node state and their upkeep, and which of those must match
exactly) lives in the configuration's own module,
`bench/configs/<config>.py` (`bench/lib/cell.py::config_module`), which
every function here that needs it takes first, as `arch`.

One lag-one training step (TGN, Rossi et al. 2020; PRES, Su et al. 2024,
Alg. 2): the previous temporal batch updates the memory, then the current
batch and its negatives are scored from embeddings of the updated memory.

* MESSAGE: for each endpoint occurrence (sources, then destinations)
  m = MLP([s_self, s_other, e, cos(dt w + b)]), dt = t - last_update.
* MEMORY: s_meas = GRU(m, s_self), or tanh(m W + s U + b) for an RNN cell.
* PRES: s_pred = s_self + clip(c * mean_delta, +-clip), where c counts the
  node's occurrences in the batch and mean_delta is the GMM trackers'
  mixture mean; fused = (1 - g) s_pred + g s_meas with g = sigmoid(gamma);
  the delta rate (fused - s_self) / max(c, 1) feeds the trackers.
  Each node's chronologically last occurrence (ties: the later one in
  source-then-destination order) writes its fused row and time.
* EMBEDDING: the configuration's module (`arch.embed`), from the updated
  memory, the last-update times and the neighbour rings.
* DECODER: relu([h_src, h_dst] W1 + b1) W2 + b2; loss = masked mean BCE
  over positives and negatives + beta * (1 - cos(s_prev, fused)) over the
  written rows (PRES Eq. 10).
* Adam on every weight, then the trackers take the batch's delta rates,
  the neighbour rings take the previous batch (the last K per node), and
  the module's own tables are kept up (`arch.maintain_extra`).

Departures of the repository's architecture from the publications, which
this reference shares because it checks the program: the message is a
two-layer MLP (TGN's default is the identity message) and there is no
dropout. A module names its own departures.

`dtype` is the precision every model value is computed in: float32 for the
reference (under `highest` matmul precision), bfloat16 for the control.
Timestamps and time differences stay float32 in both. `precision` sets
the matmul precision apart (the calibration's diagnosis reads the float32
reference at the TPU's default precision, bf16 passes, as the program's
XLA code runs).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

N_COMPONENTS = 2   # PRES GMM components (positive / negative event types)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def param_shapes(arch, m: dict, d_edge: int) -> dict:
    """Shapes of every weight, by the names the program's tree uses."""
    d_mem, d_msg, d_time, d_emb = m["d_mem"], m["d_msg"], m["d_time"], \
        m["d_embed"]
    gates = 3 if m["memory_cell"] == "gru" else 1
    return {
        "time": {"w": (d_time,), "b": (d_time,)},
        "msg": {"w1": (2 * d_mem + d_edge + d_time, d_msg), "b1": (d_msg,),
                "w2": (d_msg, d_msg), "b2": (d_msg,)},
        "mem": {"w": (d_msg, gates * d_mem), "u": (d_mem, gates * d_mem),
                "b": (gates * d_mem,)},
        "dec": {"w1": (2 * d_emb, d_emb), "b1": (d_emb,), "w2": (d_emb, 1),
                "b2": (1,)},
        "node_cls": {"w1": (d_emb, d_emb), "b1": (d_emb,), "w2": (d_emb, 1),
                     "b2": (1,)},
        "pres": {"gamma_logit": ()},
        "emb": arch.emb_shapes(m, d_edge),
    }


def init_params(arch, key, m: dict, d_edge: int):
    """Seeded weights, made on the device in one call: matrices
    N(0, 1/fan_in), biases and gamma zero, the time encoder at TGN's fixed
    frequencies 10 ** -linspace(0, 9, d_time)."""
    shapes = param_shapes(arch, m, d_edge)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))

    def make(keys):
        out = []
        for (path, shape), k in zip(flat, keys):
            name = "/".join(p.key for p in path)
            if name == "time/w":
                v = 1.0 / 10.0 ** jnp.linspace(0.0, 9.0, shape[0])
            elif len(shape) < 2:
                v = jnp.zeros(shape)
            else:
                v = jax.random.normal(k, shape) / math.sqrt(shape[0])
            out.append(v.astype(jnp.float32))
        return out

    return jax.tree_util.tree_unflatten(tree, jax.jit(make)(keys))


# ---------------------------------------------------------------------------
# state and batches
# ---------------------------------------------------------------------------


def init_state(arch, n_nodes: int, m: dict, dtype=jnp.float32) -> dict:
    """The node state every MDGNN keeps, and the module's own tables."""
    d, k = m["d_mem"], m["n_neighbors"]
    return {
        "mem": jnp.zeros((n_nodes, d), dtype),
        "last_update": jnp.zeros((n_nodes,), jnp.float32),
        "nbr": jnp.full((n_nodes, k), -1, jnp.int32),
        "nbr_t": jnp.zeros((n_nodes, k), jnp.float32),
        "ptr": jnp.zeros((n_nodes,), jnp.int32),
        "pres_n": jnp.zeros((n_nodes, N_COMPONENTS), dtype),
        "pres_xi": jnp.zeros((n_nodes, N_COMPONENTS, d), dtype),
        "pres_psi": jnp.zeros((n_nodes, N_COMPONENTS, d), dtype),
        **arch.extra_state(n_nodes, m, dtype),
    }


def batch(src, dst, t, feat, i: int, size: int) -> dict:
    """Temporal batch i of the stream, zero-padded to `size` events."""
    lo, hi = i * size, min((i + 1) * size, len(src))
    pad = size - (hi - lo)

    def cut(a):
        a = a[lo:hi]
        return np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])

    return {"src": cut(src), "dst": cut(dst), "t": cut(t), "feat": cut(feat),
            "mask": np.arange(size) < (hi - lo)}


def negatives(key, pos: dict, dst_lo: int, dst_hi: int) -> dict:
    """One corrupted event per positive: a random positive's source and
    time, a uniform destination, zero features (one key draws both)."""
    n = pos["src"].shape[0]
    idx = jax.random.randint(key, (n,), 0, n)
    dst = jax.random.randint(key, (n,), dst_lo, dst_hi)
    return {"src": jnp.asarray(pos["src"])[idx], "dst": dst.astype(jnp.int32),
            "t": jnp.asarray(pos["t"])[idx],
            "feat": jnp.zeros_like(jnp.asarray(pos["feat"])),
            "mask": jnp.asarray(pos["mask"])[idx]}


def step_keys(key, n_steps: int):
    """The per-step negative-sampling keys of one epoch: split the epoch
    key once per step and keep the second half."""
    out = []
    for _ in range(n_steps):
        key, sub = jax.random.split(key)
        out.append(sub)
    return out


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def time_enc(p, dt, dtype):
    """TGN's time encoding cos(dt w + b), computed in float32."""
    return jnp.cos(dt[..., None] * p["w"].astype(jnp.float32)
                   + p["b"].astype(jnp.float32)).astype(dtype)


def _cell(m, p, x, h):
    if m["memory_cell"] == "gru":
        d = h.shape[-1]
        gx = x @ p["w"] + p["b"]
        gh = h @ p["u"]
        r = jax.nn.sigmoid(gx[:, :d] + gh[:, :d])
        z = jax.nn.sigmoid(gx[:, d:2 * d] + gh[:, d:2 * d])
        n = jnp.tanh(gx[:, 2 * d:] + r * gh[:, 2 * d:])
        return (1 - z) * h + z * n
    return jnp.tanh(x @ p["w"] + h @ p["u"] + p["b"])


def _last_flags(nodes, times, mask, n_nodes):
    """True at each node's chronologically last valid occurrence (ties:
    the later index)."""
    seg = jnp.where(mask, nodes, n_nodes)
    t_max = jax.ops.segment_max(jnp.where(mask, times, -jnp.inf), seg,
                                num_segments=n_nodes + 1)
    at_max = mask & (times == t_max[seg])
    idx = jnp.arange(nodes.shape[0])
    i_max = jax.ops.segment_max(jnp.where(at_max, idx, -1), seg,
                                num_segments=n_nodes + 1)
    return at_max & (idx == i_max[seg])


def memory_stage(m, cfg, params, state, prev, dtype):
    """MESSAGE + MEMORY + PRES over the previous batch. Returns the new
    memory and last-update tables and the per-occurrence rows."""
    n_nodes = state["mem"].shape[0]
    nodes = jnp.concatenate([prev["src"], prev["dst"]])
    other = jnp.concatenate([prev["dst"], prev["src"]])
    times = jnp.concatenate([prev["t"], prev["t"]])
    feat = jnp.concatenate([prev["feat"], prev["feat"]]).astype(dtype)
    mask = jnp.concatenate([prev["mask"], prev["mask"]])
    s_self = state["mem"][nodes]
    s_other = state["mem"][other]
    t_enc = time_enc(params["time"], times - state["last_update"][nodes],
                     dtype)
    pm = params["msg"]
    x = jnp.concatenate([s_self, s_other, feat, t_enc], axis=-1)
    msg = jax.nn.relu(x @ pm["w1"] + pm["b1"]) @ pm["w2"] + pm["b2"]
    s_meas = _cell(m, params["mem"], msg, s_self)
    # PRES predict (Eq. 7) and correct (Eq. 8)
    seg = jnp.where(mask, nodes, n_nodes)
    count = jax.ops.segment_sum(mask.astype(dtype), seg,
                                num_segments=n_nodes + 1)[nodes]
    pn, xi = state["pres_n"][nodes], state["pres_xi"][nodes]
    total = jnp.sum(pn, axis=1, keepdims=True)
    alpha = jnp.where(total > 0, pn / jnp.maximum(total, 1e-6),
                      1.0 / N_COMPONENTS).astype(dtype)
    mu = xi / jnp.maximum(pn, 1.0)[..., None]
    mean_delta = jnp.sum(alpha[..., None] * mu, axis=1)
    clip = cfg["pres_clip"]
    s_pred = s_self + jnp.clip(count[:, None] * mean_delta, -clip, clip)
    g = jax.nn.sigmoid(params["pres"]["gamma_logit"]).astype(dtype)
    fused = (1 - g) * s_pred + g * s_meas
    delta = (fused - s_self) / jnp.maximum(count, 1.0)[:, None]
    sel = _last_flags(nodes, times, mask, n_nodes)
    widx = jnp.where(sel, nodes, n_nodes)
    mem = state["mem"].at[widx].set(fused, mode="drop")
    last = state["last_update"].at[widx].set(times, mode="drop")
    return mem, last, {"nodes": nodes, "other": other, "times": times,
                       "mask": mask, "sel": sel, "s_prev": s_self,
                       "fused": fused, "delta": delta}


def _decode(params, hs, hd):
    p = params["dec"]
    h = jax.nn.relu(jnp.concatenate([hs, hd], axis=-1) @ p["w1"] + p["b1"])
    return (h @ p["w2"] + p["b2"])[:, 0]


def loss_fn(params, arch, m, cfg, state, prev, pos, neg, dtype,
            half_batch=False):
    """The training loss of one step, and what the step carries on.
    `half_batch` plants a fault for the benchmark's own tests: the BCE is
    the mean over the first half of the batch only."""
    mem, last, occ = memory_stage(m, cfg, params, state, prev, dtype)
    b = pos["src"].shape[0]
    rows = jnp.concatenate([pos["src"], pos["dst"], neg["src"], neg["dst"]])
    tq = jnp.concatenate([pos["t"], pos["t"], neg["t"], neg["t"]])
    h = arch.embed(m, params, mem, last, state, rows, tq, dtype)
    lp = _decode(params, h[:b], h[b:2 * b])
    ln = _decode(params, h[2 * b:3 * b], h[3 * b:])
    pmask, nmask = pos["mask"].astype(dtype), neg["mask"].astype(dtype)
    if half_batch:
        keep = (jnp.arange(b) < b // 2).astype(dtype)
        pmask, nmask = pmask * keep, nmask * keep
    bce = (jnp.sum(jax.nn.softplus(-lp) * pmask)
           + jnp.sum(jax.nn.softplus(ln) * nmask)) \
        / jnp.maximum(jnp.sum(pmask) + jnp.sum(nmask), 1.0)
    w = (occ["sel"] & occ["mask"]).astype(dtype)[:, None]
    a = (occ["s_prev"] * w).reshape(-1)
    c = (occ["fused"] * w).reshape(-1)
    cos = jnp.dot(a, c) / (jnp.linalg.norm(a) * jnp.linalg.norm(c) + 1e-8)
    loss = bce + cfg["beta"] * (1 - cos)
    return loss, (mem, last, occ)


def _ring_append(state, occ, k):
    """Append each occurrence's (other endpoint, time) to its node's ring
    of the K most recent neighbours, in occurrence order."""
    n_nodes = state["nbr"].shape[0]
    nodes, mask = occ["nodes"], occ["mask"]
    seg = jnp.where(mask, nodes, n_nodes)
    count = jax.ops.segment_sum(mask.astype(jnp.int32), seg,
                                num_segments=n_nodes + 1)
    order = jnp.argsort(seg, stable=True)
    first = jnp.searchsorted(seg[order], jnp.arange(n_nodes + 1))
    rank = jnp.zeros_like(nodes).at[order].set(
        jnp.arange(nodes.shape[0]) - first[seg[order]])
    keep = mask & (rank >= count[seg] - k)     # only the last K survive
    slot = (state["ptr"][nodes] + rank) % k
    flat = jnp.where(keep, nodes * k + slot, n_nodes * k)
    nbr = state["nbr"].reshape(-1).at[flat].set(occ["other"], mode="drop")
    nbr_t = state["nbr_t"].reshape(-1).at[flat].set(occ["times"],
                                                    mode="drop")
    ptr = (state["ptr"] + count[:n_nodes]) % k
    return nbr.reshape(n_nodes, k), nbr_t.reshape(n_nodes, k), ptr


def adam(opt_cfg, params, grads, opt, dtype):
    b1, b2, eps, lr = (opt_cfg["b1"], opt_cfg["b2"], opt_cfg["eps"],
                       opt_cfg["lr"])
    step = opt["step"] + 1
    mu = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"],
                      grads)
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m_, v: (p - lr * (m_ / bc1.astype(dtype))
                          / (jnp.sqrt(v / bc2.astype(dtype)) + eps)
                          ).astype(dtype),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "step": step}


def maintain(arch, m, params, state, prev, mem, last, occ, dtype):
    """The state after a batch `prev`: its memory and times, the trackers
    with the batch's delta rates (Eq. 9, component 0), the neighbour rings
    with its events, and the module's own tables (`params`: the weights
    after the step)."""
    n_rows = state["pres_n"].size
    flat = jnp.where(occ["sel"] & occ["mask"], occ["nodes"] * N_COMPONENTS,
                     n_rows)
    delta = jax.lax.stop_gradient(occ["delta"])

    def add(table, rows):
        t2 = table.reshape((n_rows,) + table.shape[2:])
        return t2.at[flat].add(rows, mode="drop").reshape(table.shape)

    nbr, nbr_t, ptr = _ring_append(state, occ, m["n_neighbors"])
    new = {
        "mem": jax.lax.stop_gradient(mem), "last_update": last,
        "nbr": nbr, "nbr_t": nbr_t, "ptr": ptr,
        "pres_n": add(state["pres_n"], jnp.ones(flat.shape, dtype)),
        "pres_xi": add(state["pres_xi"], delta),
        "pres_psi": add(state["pres_psi"], delta * delta),
    }
    return {**new, **arch.maintain_extra(m, params, state, new, prev, occ,
                                         dtype)}


def train_step(arch, m, cfg, opt_cfg, dtype, half_batch, params, opt, state,
               prev, pos, neg):
    """One reference training step. Returns (params, opt, state, loss,
    grads)."""
    (loss, (mem, last, occ)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, arch, m, cfg, state, prev, pos, neg,
                               dtype, half_batch)
    params, opt = adam(opt_cfg, params, grads, opt, dtype)
    return (params, opt,
            maintain(arch, m, params, state, prev, mem, last, occ, dtype),
            loss, grads)


def fold(arch, m, cfg, dtype, params, state, events):
    """Serving: fold a batch of events into the state (no training)."""
    mem, last, occ = memory_stage(m, cfg, params, state, events, dtype)
    return maintain(arch, m, params, state, events, mem, last, occ, dtype)


def link_scores(arch, m, dtype, params, state, src, dst, t):
    """Serving: link logits of (src, dst) pairs at times t."""
    b = src.shape[0]
    h = arch.embed(m, params, state["mem"], state["last_update"], state,
                   jnp.concatenate([src, dst]), jnp.concatenate([t, t]),
                   dtype)
    return _decode(params, h[:b], h[b:])


def item_scores(arch, m, dtype, params, state, src, t, items):
    """Serving: logits of every source against every item, the items
    embedded once at the latest query time of the request."""
    b, n = src.shape[0], items.shape[0]
    t_item = jnp.full((n,), jnp.max(t), jnp.float32)
    h = arch.embed(m, params, state["mem"], state["last_update"], state,
                   jnp.concatenate([src, items]),
                   jnp.concatenate([t, t_item]), dtype)
    hs, hi = h[:b], h[b:]
    pair = jnp.concatenate([jnp.repeat(hs, n, axis=0),
                            jnp.tile(hi, (b, 1))], axis=-1)
    p = params["dec"]
    z = jax.nn.relu(pair @ p["w1"] + p["b1"])
    return (z @ p["w2"] + p["b2"])[:, 0].reshape(b, n)


@functools.lru_cache(maxsize=None)
def _jitted_step(arch, m_items, cfg_items, opt_items, dtype_name,
                 half_batch):
    m, cfg, opt_cfg = dict(m_items), dict(cfg_items), dict(opt_items)
    dtype = jnp.dtype(dtype_name)
    fn = functools.partial(train_step, arch, m, cfg, opt_cfg, dtype,
                           half_batch)
    return jax.jit(fn)


def run(arch, model: dict, params, stream, batch_size: int, dst_range, key,
        n_steps: int, dtype=jnp.float32, half_batch=False, precision=None):
    """`n_steps` reference steps from the seeded weights and an empty
    state, over batches 0..n_steps of `stream` = (src, dst, t, feat), with
    the negatives the epoch key draws. Returns per step
    {"loss", "grads"} and the params and state after the last step."""
    m = model["model"]
    cfg = {"pres_clip": m["pres_clip"], "beta": m["beta"]}
    opt_cfg = {k: model["optimizer"][k] for k in ("lr", "b1", "b2", "eps")}
    fn = _jitted_step(arch, tuple(sorted(m.items())),
                      tuple(sorted(cfg.items())),
                      tuple(sorted(opt_cfg.items())), jnp.dtype(dtype).name,
                      half_batch)
    n_nodes = model["n_nodes"]
    params = jax.tree.map(lambda p: jnp.asarray(p).astype(dtype), params)
    opt = {"mu": jax.tree.map(jnp.zeros_like, params),
           "nu": jax.tree.map(jnp.zeros_like, params),
           "step": jnp.zeros((), jnp.int32)}
    state = init_state(arch, n_nodes, m, dtype)
    keys = step_keys(key, n_steps)
    out = []
    prec = precision or ("highest" if jnp.dtype(dtype) == jnp.float32
                         else "default")
    with jax.default_matmul_precision(prec):
        prev = batch(*stream, 0, batch_size)
        prev = {k: jnp.asarray(v) for k, v in prev.items()}
        for i in range(n_steps):
            pos = {k: jnp.asarray(v) for k, v in
                   batch(*stream, i + 1, batch_size).items()}
            neg = negatives(keys[i], pos, *dst_range)
            params, opt, state, loss, grads = fn(params, opt, state, prev,
                                                 pos, neg)
            out.append({"loss": float(loss),
                        "grads": jax.device_get(grads) if i == 0 else None})
            prev = pos
    return out, jax.device_get(params), jax.device_get(state)
