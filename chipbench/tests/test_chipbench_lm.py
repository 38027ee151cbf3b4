"""The LM kind (``kinds/lm.py``) through ``harness.run_cell`` on the CPU.

A copy of the benchmark gains a tiny cell of the DeepSeek-V2 smoke
configuration (``program.size`` "smoke", float32, 2 sequences of 32
tokens): a sound run is ``correct``; the same run with the program's
router bound by a capacity of 1.25 (the dispatch the expert layer had
before it was dropless) is not.  The dsv2lite cell's own counts are
checked against the configuration's published sizes.
"""

import json
import math
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, spec
from repro.configs import smoke_config
from repro.models import moe

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 5
WINDOW_S = 0.3


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("lm")
    pkg = root / "chipbench"
    shutil.copytree(ROOT / "chipbench", pkg,
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    kind = spec._load_module(pkg / "kinds" / "lm.py", "kind")
    conf = json.loads((ROOT / "chipbench/configs/dsv2lite.json").read_text())
    conf.update(kind.stated(smoke_config("deepseek-v2-lite")))
    conf["program"] = {"arch": "deepseek-v2-lite", "size": "smoke"}
    (pkg / "configs/tiny.json").write_text(json.dumps(conf))
    (pkg / "traffic/tiny.json").write_text(json.dumps(
        {"sequences": 16, "seq_len": 32, "batch": 2, "zipf_a": 1.2}))
    (pkg / "workloads/tiny.b2.json").write_text(json.dumps({
        "warmup_steps": 1, "trace_steps": 2,
        "limits": {"loss_gap": 1e-5, "grad_norm_gap": 1e-4,
                   "update_norm_gap": 1e-4}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny",
                             "file": "chipbench/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny.b2", "config": "tiny",
                               "traffic": "tiny", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return spec.load_cell("tiny.b2", root=root)


def _run(cell):
    return harness.run_cell(cell, SEED, WINDOW_S, False, jax.devices()[:1],
                            time.perf_counter(), kernel_mode="ref")


def test_lm_kind_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_lm_kind_with_a_capacity_drop_is_not_correct(cell, monkeypatch):
    """Each expert keeps at most ceil(1.25 · T · k / E) of every group of
    T tokens, in token order, and drops the rest."""
    route = moe.route
    ref = spec.load_reference(cell)

    def capacity_route(router, x, cfg):
        r = route(router, x, cfg)
        chose = jnp.sum(jax.nn.one_hot(r.experts, cfg.n_experts), axis=1)
        keep = ref._capacity_keep(chose, {
            "num_experts_per_tok": cfg.experts_per_token,
            "experts_routed_over": cfg.n_experts})
        kept = jnp.take_along_axis(keep, r.experts, axis=1)
        return r._replace(weights=r.weights * kept)

    monkeypatch.setattr(moe, "route", capacity_route)
    result = _run(cell)
    assert not result["correct"], result["checks"]


def test_dsv2lite_counts():
    cell = spec.load_cell("dsv2lite.s4096.b2")
    kind = spec.load_kind(cell)
    assert kind.samples_per_step(cell) == 2
    # 310.4 M multiply-adds a token forward, 6x for training, 4096 tokens
    assert kind.flops_per_sample(cell) == pytest.approx(7.63e12, rel=2e-3)
    names = [c.kernel for c in kind.kernel_calls(cell)]
    assert names[:2] == ["softmax_xent_fwd", "softmax_xent_dlogits"]
    tokens = 2 * 4096
    xent = kind.kernel_calls(cell)[0]
    assert xent.bytes == 4 * tokens * 12800 + 4 * tokens + 8 * tokens
    assert math.isclose(sum(c.flops for c in kind.kernel_calls(cell)
                            if c.kernel == "ragged_dot"),
                        4 * 3 * 3 * 2 * tokens * 6 * 8 / 64 * 2048 * 1408)
