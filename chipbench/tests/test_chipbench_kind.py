"""A model kind added as files alone runs through the harness (CPU).

A copy of the benchmark gains a second kind, a bigram language model fed
token sequences by ``repro.data.Batcher``, as new files only: its
configuration, ``kinds/bigram.py``, ``references/bigram.py``, traffic,
cell parameters and a ``BENCHMARK.json`` entry.  ``harness.run_cell``
drives it with no edit to any file the benchmark has: a sound run is
``correct``, a fault planted in its step is not.
"""

import json
import shutil
import time
from pathlib import Path

import jax
import pytest

from chipbench import harness, spec

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 777
WINDOW_S = 0.3

KIND = '''"""A bigram language model: each token's embedding times an output
matrix gives the next token's logits; SGD with momentum.  A sample is one
sequence."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks
from repro.data import Batcher


def dataset(cell, seed):
    c = cell.config
    return np.random.default_rng(seed).integers(
        0, c["vocab"], (cell.traffic["samples"], c["seq_len"]),
        dtype=np.int32)


def loss(params, tokens):
    logits = params["embed"][tokens[:, :-1]] @ params["head"]["w"]
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def build(cell, devices, kernel_mode):
    o = cell.config["optimizer"]

    def step(state, batch):
        value, grads = jax.value_and_grad(loss)(state["params"],
                                                batch["tokens"])
        mom = jax.tree.map(lambda m, g: o["momentum"] * m + g,
                           state["mom"], grads)
        params = jax.tree.map(lambda p, m: p - o["lr"] * m,
                              state["params"], mom)
        return {"params": params, "mom": mom}, {"loss": value}
    return devices[0], jax.jit(step)


def init_state(cell, program, seed):
    c = cell.config
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed & 0xFFFFFFFF))
    params = {"embed": jax.random.normal(k1, (c["vocab"], c["width"])),
              "head": {"w": jax.random.normal(k2, (c["width"], c["vocab"]))
                       / c["width"] ** 0.5}}
    return jax.device_put(
        {"params": params, "mom": jax.tree.map(jnp.zeros_like, params)},
        program)


def feed(cell, program, data):
    return Batcher({"tokens": data}, batch_size=cell.traffic["batch"])


def _norms(tree):
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(leaf))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def first_steps(cell, step, state, feed):
    p0 = state["params"]
    losses = []
    for t in range(checks.CHECK_STEPS):
        state, metrics = step(state, next(feed))
        losses.append(float(metrics["loss"]))
        if t == 0:
            grads = _norms(state["mom"])   # the momentum after one step
    change = _norms(jax.tree.map(jnp.subtract, state["params"], p0))
    return state, checks.Readings(tuple(losses), grads, change)


def reference(cell, seed, data, device, planted=None):
    from chipbench import spec
    rows = data[:checks.CHECK_STEPS * cell.traffic["batch"]]
    return spec.load_reference(cell).train(
        seed, cell.config, rows.reshape(checks.CHECK_STEPS, -1,
                                        rows.shape[1]))


def samples_per_step(cell):
    return cell.traffic["batch"]


def flops_per_sample(cell):
    c = cell.config
    return 6 * (c["seq_len"] - 1) * c["width"] * c["vocab"]


def kernel_calls(cell):
    return []
'''

REFERENCE = '''"""The bigram model's plain reference: float64 NumPy with the gradients
written out, weights drawn by the kind's recipe."""

import jax
import numpy as np

from chipbench.checks import Readings


def train(seed, config, steps):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed & 0xFFFFFFFF))
    v, d = config["vocab"], config["width"]
    emb = np.asarray(jax.random.normal(k1, (v, d)), np.float64)
    head = np.asarray(jax.random.normal(k2, (d, v)) / d ** 0.5, np.float64)
    e0, h0 = emb.copy(), head.copy()
    lr, mu = config["optimizer"]["lr"], config["optimizer"]["momentum"]
    me, mh = np.zeros_like(emb), np.zeros_like(head)
    losses = []
    for t, tokens in enumerate(steps):
        x, y = tokens[:, :-1].ravel(), tokens[:, 1:].ravel()
        h = emb[x]
        z = h @ head
        z = z - z.max(axis=1, keepdims=True)
        p = np.exp(z) / np.exp(z).sum(axis=1, keepdims=True)
        losses.append(float(np.mean(-np.log(p[np.arange(len(y)), y]))))
        dz = p.copy()
        dz[np.arange(len(y)), y] -= 1.0
        dz /= len(y)
        gh = h.T @ dz
        ge = np.zeros_like(emb)
        np.add.at(ge, x, dz @ head.T)
        me, mh = mu * me + ge, mu * mh + gh
        emb, head = emb - lr * me, head - lr * mh
        if t == 0:
            grads = {"['embed']": float(np.linalg.norm(ge)),
                     "['head']['w']": float(np.linalg.norm(gh))}
    change = {"['embed']": float(np.linalg.norm(emb - e0)),
              "['head']['w']": float(np.linalg.norm(head - h0))}
    return Readings(tuple(losses), grads, change)
'''


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """The bigram cell in a copy of the benchmark, added as files only."""
    root = tmp_path_factory.mktemp("kind")
    pkg = root / "chipbench"
    shutil.copytree(ROOT / "chipbench", pkg,
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    added = {
        "configs/bigram.json": json.dumps({
            "name": "bigram", "kind": "bigram", "reference": "bigram",
            "vocab": 32, "width": 16, "seq_len": 9,
            "optimizer": {"lr": 0.5, "momentum": 0.9}}),
        "kinds/bigram.py": KIND,
        "references/bigram.py": REFERENCE,
        "traffic/tok512.b16.json": json.dumps({"samples": 512,
                                               "batch": 16}),
        "workloads/bigram.b16.json": json.dumps({
            "warmup_steps": 2, "trace_steps": 2,
            "limits": {"loss_gap": 1e-5, "grad_norm_gap": 1e-4,
                       "update_norm_gap": 1e-4}}),
    }
    for name, text in added.items():
        assert not (pkg / name).exists()
        (pkg / name).write_text(text)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "bigram",
                             "file": "chipbench/configs/bigram.json"})
    bench["workloads"].append({"name": "bigram.b16", "config": "bigram",
                               "traffic": "tok512.b16", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return spec.load_cell("bigram.b16", root=root)


def _run(cell):
    return harness.run_cell(cell, SEED, WINDOW_S, False, jax.devices()[:1],
                            time.perf_counter())


def test_kind_added_as_files_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"samples_per_s", "setup_s"}
    assert set(result["checks"]) == {"loss_gap", "grad_norm_gap",
                                     "update_norm_gap"}


def test_kind_added_as_files_with_a_fault_is_not_correct(cell, monkeypatch):
    """The loss taken over the first half of the batch only."""
    kind = spec.load_kind(cell)
    whole = kind.loss
    monkeypatch.setattr(kind, "loss", lambda params, tokens: whole(
        params, tokens[:tokens.shape[0] // 2]))
    result = _run(cell)
    assert not result["correct"], result["checks"]
