"""Work counters against figures worked out by hand, and the readers that
use them."""

from pathlib import Path

import pytest

from chipbench import harness, spec, trace, work

NN1 = [784, 1000, 500, 10]
NN6 = [1024, 4000, 1000, 4000, 1000, 4000, 1000, 4000, 10]


@pytest.mark.parametrize("sizes, flops", [(NN1, 6_166_000),
                                          (NN6, 160_624_000)])
def test_model_flops_per_sample(sizes, flops):
    assert work.model_flops_per_sample(sizes) == flops


def test_nn1_first_layer_call_bytes_and_flops():
    # x (128, 784), w (784, 1000), b (1000,), y (128, 1000), float32
    fwd = work.fcnn_fwd(128, 784, 1000)
    assert fwd.flops == 2 * 128 * 784 * 1000 == 200_704_000
    assert fwd.bytes == 4 * (100_352 + 784_000 + 1_000 + 128_000) == 4_053_408
    # dgrad reads dy, y (128, 1000) and w; writes dx (128, 784)
    assert work.fcnn_dgrad(128, 784, 1000).bytes == 4 * (
        256_000 + 784_000 + 100_352)
    # wgrad reads x, dy, y; writes dw and db
    assert work.fcnn_wgrad(128, 784, 1000).bytes == 4 * (
        100_352 + 256_000 + 784_000 + 1_000)


@pytest.mark.parametrize("sizes, degrees", [(NN1, None), (NN1, [4, 4, 2]),
                                            (NN6, None),
                                            (NN6, [4] * 7 + [2])])
def test_step_calls_total_model_flops(sizes, degrees):
    """Chunking a layer over d chips splits its operations, never adds."""
    calls = work.step_calls(sizes, 128, degrees)
    assert sum(c.flops for c in calls) == 128 * work.model_flops_per_sample(
        sizes)


def test_step_calls_per_layer_kinds():
    calls = work.step_calls(NN1, 128, [4, 4, 2])
    kinds = [c.kernel for c in calls]
    assert kinds.count("fcnn_layer") == 10
    assert kinds.count("fcnn_layer_wgrad") == 10
    assert kinds.count("fcnn_layer_dgrad") == 6      # layers 2 and 3 only
    assert kinds[-2:] == ["softmax_xent_fwd", "softmax_xent_dlogits"]
    with pytest.raises(ValueError):
        work.step_calls(NN1, 128, [3, 1, 1])


def test_least_time_takes_the_larger_bound():
    calls = [work.Call("a", flops=2e12, bytes=1e9),    # 10 ms vs 1 ms
             work.Call("b", flops=1e9, bytes=4e9)]     # 5 us vs 4 ms
    assert work.least_time_s(calls, 2e14, 1e12) == pytest.approx(0.014)


@pytest.fixture(scope="module")
def recorded():
    """The reduction of ten NN1 batch-128 steps traced on one TPU v5e, and
    its profile."""
    from chipbench import scopes
    profile = scopes.load(Path(__file__).resolve().parents[1] / "testdata"
                          / "nn1.b128.10steps.scoped.xplane.pb.gz")
    return trace.reduce(profile.trace), profile


@pytest.mark.parametrize("name, sizes, batch, degrees", [
    ("nn1.b128", NN1, 128, [1, 1, 1]),
    ("nn6.b1024", NN6, 1024, [1] * 8),
    ("nn6.b128", NN6, 128, [1] * 8)])
def test_mfu_and_roofline_from_the_kinds_counts(recorded, name, sizes,
                                                batch, degrees):
    """``mfu`` and ``kernel_roofline`` read the kind's counts from the
    context and give what the FCNN work counters give for the cell."""
    summary, profile = recorded
    cell = spec.load_cell(name)
    kind = spec.load_kind(cell)
    peak = harness.peaks("TPU v5 lite", cell.pkg)
    ctx = harness.Context(
        cell=cell, flops_per_sample=kind.flops_per_sample(cell),
        kernel_calls=kind.kernel_calls(cell), steps=10, host={},
        summary=summary, profile=profile, peak=peak, samples_per_s=1e5)
    flops = work.model_flops_per_sample(sizes) * 1e5
    assert spec.load_reader(cell, "mfu")(ctx) == (
        100.0 * flops / (1 * peak["bf16_flops_per_s"]))
    kernel_s = summary.mean("kernel_ns") * 1 / 1e9
    least = 10 * work.least_time_s(work.step_calls(sizes, batch, degrees),
                                   peak["bf16_flops_per_s"],
                                   peak["hbm_bytes_per_s"])
    assert spec.load_reader(cell, "kernel_roofline")(ctx) == (
        100.0 * least / kernel_s)
