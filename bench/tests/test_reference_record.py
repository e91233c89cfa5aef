"""The plain reference, held bit for bit to a record of its own readings on
the tiny streams (`data/reference.tgn-pres.tiny.json`), taken before the
configurations' modules were split out of it:

* training (`data/train.tiny.json`, `check_steps` steps): each step's loss,
  the first gradient's leaf norms, and a SHA-256 digest of every weight
  before and after the steps and of every state table after them;
* serving (`data/serve.tiny.json`): the digests of the weights, of the link
  scores and top-k scores of one round after the prefix, and of every
  state table after its fold.

The readings are taken in a child process held to one CPU: XLA's CPU
backend splits some reductions by the number of cores a process may use,
so the same program reads other last bits on another count.

    python3 bench/tests/test_reference_record.py   # prints the readings
"""
import hashlib
import json
import os
import pathlib
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
RECORD = BENCH / "tests" / "data" / "reference.tgn-pres.tiny.json"
TRAIN_SEED = 2**33 + 101     # test_harness.py's seed
SERVE_SEED = 2**33 + 202     # test_serve_harness.py's seed


def _digest(a) -> str:
    import numpy as np
    a = np.ascontiguousarray(np.asarray(a))
    h = hashlib.sha256(f"{a.dtype.str}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()


def readings() -> dict:
    import jax
    import numpy as np

    from bench.lib import cell, compare, reference, serve, train

    def digests(tree):
        return {k: _digest(v) for k, v in compare._leaves(tree).items()}

    config = cell.load_json(BENCH / "configs" / "tgn-pres.json")
    arch = cell.config_module("tgn-pres")
    m = config["model"]

    tiny = cell.load_json(BENCH / "tests" / "data" / "train.tiny.json")
    g = tiny["graph"]
    key = train.seed_key(TRAIN_SEED)
    params0 = jax.device_get(reference.init_params(
        arch, jax.random.fold_in(key, 0), m, g["feat_dim"]))
    stream = train.make_stream(tiny, TRAIN_SEED)
    dst = (g["n_users"], g["n_users"] + g["n_items"])
    steps, p_end, s_end = reference.run(
        arch, train.model_spec(config, tiny), params0, stream,
        tiny["batch_size"], dst, jax.random.fold_in(key, 1),
        tiny["check_steps"])
    out = {"train": {"seed": TRAIN_SEED,
                     "losses": [s["loss"] for s in steps],
                     "grad_norms": compare._norms(steps[0]["grads"]),
                     "params0": digests(params0), "params": digests(p_end),
                     "state": digests(s_end)}}

    tiny = cell.load_json(BENCH / "tests" / "data" / "serve.tiny.json")
    g = tiny["graph"]
    key = train.seed_key(SERVE_SEED)
    params0 = jax.device_get(reference.init_params(
        arch, jax.random.fold_in(key, 0), m, g["feat_dim"]))
    stream, neg, _ = serve.make_stream(tiny, SERVE_SEED, 0.5,
                                       tiny["rate_events_per_s"])
    a = tiny["prefix_events"]
    b = a + 100
    ask = np.arange(a, b)[(np.arange(a, b) - a) % tiny["topk_every"] == 0]
    items = (g["n_users"], g["n_users"] + g["n_items"])
    ref = serve.replay(config, tiny, params0, stream, neg, a, [(a, b)],
                       [ask], tiny["topk"], items)
    out["serve"] = {"seed": SERVE_SEED, "round": [a, b],
                    "params0": digests(params0),
                    "scores": _digest(ref["scores"]),
                    "topk_all": [_digest(x) for x in ref["topk_all"]],
                    "state": digests(ref["state"])}
    return out


def _differences(got, want, path=""):
    if isinstance(want, dict) and isinstance(got, dict):
        out = [f"{path}/{k}: missing or extra"
               for k in set(got) ^ set(want)]
        for k in sorted(set(got) & set(want)):
            out += _differences(got[k], want[k], f"{path}/{k}")
        return out
    return [] if got == want else [f"{path}: {got!r} != {want!r}"]


def test_reference_reproduces_its_record():
    p = subprocess.run([sys.executable, __file__], capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.splitlines()[-1])
    want = json.loads(RECORD.read_text())
    diffs = [d for part in ("train", "serve")
             for d in _differences(got[part], want[part], part)]
    assert not diffs, diffs


if __name__ == "__main__":
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    print(json.dumps(readings(), sort_keys=True))
