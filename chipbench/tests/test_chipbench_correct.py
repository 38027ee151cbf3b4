"""``correct`` comes out false for the control and for each fault that a
training cell can have, and true for a sound run (CPU, NN1 at batch 128).

The run skips the harness's look for a chip (CPU devices, the program's
jnp kernel dispatch) and drives the rest of it: data, the program's
executable and step, the first three steps, the window, the reference and
the comparison against the cell's own limits.
"""

import json
import shutil
import time
from pathlib import Path

import jax
import pytest

from chipbench import calibrate, checks, harness, spec

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 12345        # wider than 32 bits, as the driver's may be
WINDOW_S = 0.5


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell("nn1.b128")


@pytest.fixture(scope="module")
def cell_x4(tmp_path_factory):
    """nn1.b128 on a four-device ring, with the limits of nn1.b128."""
    root = tmp_path_factory.mktemp("x4")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "nn1.b128.x4", "config": "nn1",
                               "traffic": "gmm60k.b128", "chips": 4})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    params = json.loads((ROOT / "chipbench/workloads/nn1.b128.json")
                        .read_text())
    params["fp_degrees"] = [4, 4, 2]
    (root / "chipbench/workloads/nn1.b128.x4.json").write_text(
        json.dumps(params))
    return spec.load_cell("nn1.b128.x4", root=root)


def _run(cell):
    devices = jax.devices()[:cell.chips]
    return harness.run_cell(cell, SEED, WINDOW_S, False, devices,
                            time.perf_counter(), kernel_mode="ref")


def test_control_is_not_correct(cell):
    """The reference in bfloat16 in the program's place fails a limit;
    the float32 reference against itself passes."""
    _control_fails(cell)


def test_nn6_b128_control_is_not_correct():
    """As above, at NN6 width and batch 128, against that cell's limits."""
    _control_fails(spec.load_cell("nn6.b128"))


def _control_fails(cell):
    kind = spec.load_kind(cell)
    data = kind.dataset(cell, SEED)
    device = jax.devices()[0]
    ref = kind.reference(cell, SEED, data, device)
    control = kind.reference(cell, SEED, data, device, planted="control")
    limits = cell.params["limits"]
    assert checks.judge(checks.compare(ref, ref), limits)[0]
    ok, table = checks.judge(checks.compare(control, ref), limits)
    assert not ok, table


def test_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"samples_per_s", "setup_s"}


def _frozen_train_step(orig):
    def train_step(self, optimizer, grad_clip=None, donate=True):
        inner = orig(self, optimizer, grad_clip=grad_clip, donate=False)

        @jax.jit
        def step(state, batch):
            _, metrics = inner(state, batch)
            return state, metrics
        return step
    return train_step


def test_state_left_unchanged_is_not_correct(cell, monkeypatch):
    from repro.exec.api import Executable
    monkeypatch.setattr(Executable, "train_step",
                        _frozen_train_step(Executable.train_step))
    result = _run(cell)
    assert not result["correct"]
    assert result["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(cell, monkeypatch):
    from repro.kernels import ops
    orig = ops.softmax_xent

    def half(logits, labels, **kw):
        b = logits.shape[0] // 2
        return orig(logits[:b], labels[:b], **kw)
    monkeypatch.setattr(ops, "softmax_xent", half)
    assert not _run(cell)["correct"]


def test_altered_answer_is_not_correct(cell, monkeypatch):
    """The first row's label logit 10 below what the last layer made."""
    from repro.kernels import ops
    orig = ops.softmax_xent

    def altered(logits, labels, **kw):
        return orig(logits.at[0, labels[0]].add(-10.0), labels, **kw)
    monkeypatch.setattr(ops, "softmax_xent", altered)
    assert not _run(cell)["correct"]


def test_sound_run_on_four_devices_is_correct(cell_x4):
    assert _run(cell_x4)["correct"]


def test_exchange_left_out_is_not_correct(cell_x4, monkeypatch):
    monkeypatch.setattr(jax.lax, "all_gather", calibrate._no_exchange(4))
    assert not _run(cell_x4)["correct"]
