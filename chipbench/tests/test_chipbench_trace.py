"""The trace reduction: interval arithmetic, op categories, exposed
collective time and gap attribution, on hand-made traces and on traces
recorded on the chip (``chipbench/testdata``)."""

import gzip
from pathlib import Path

import pytest

from chipbench import spec
from chipbench import trace as T

DATA = Path(__file__).resolve().parents[1] / "testdata"

KERNEL = ('%fcnn_layer_dgrad.3 = f32[128,1024]{1,0:T(8,128)S(1)} custom-call('
          'f32[128,512]{1,0} %a, f32[128,512]{1,0} %b, f32[1024,512]{1,0} %c)'
          ', custom_call_target="tpu_custom_call", operand_layout_constraints'
          '={f32[128,512]{1,0}}')
XENT = ('%softmax_xent_fwd.1 = (f32[128,1]{1,0:T(8,128)S(1)}, f32[128,1]{1,0'
        ':T(8,128)S(1)}) custom-call(f32[128,128]{1,0} %x, s32[128,1]{1,0} '
        '%y), custom_call_target="tpu_custom_call"')
GATHER = ('%all-gather-start.2 = (f32[1,128,1000]{2,1,0}, f32[4,128,1000]'
          '{2,1,0}) all-gather-start(f32[1,128,1000]{2,1,0} %y), channel_id=3'
          ', replica_groups=[1,4]<=[4], dimensions={0}')
DONE = ('%all-gather-done.2 = f32[4,128,1000]{2,1,0} all-gather-done((f32[1,'
        '128,1000]{2,1,0}, f32[4,128,1000]{2,1,0}) %all-gather-start.2)')
COPY = ('%copy-start.32 = (f32[1,10]{1,0:T(1,128)}, f32[1,10]{1,0:T(1,128)S('
        '1)}, u32[]{:S(2)}) copy-start(f32[1,10]{1,0:T(1,128)S(1)} %g)')
ADAM = ('%multiply_subtract_fusion.2 = (f32[1,500,10]{2,1,0}, f32[1,500,10]'
        '{2,1,0}) fusion(f32[1,500,10]{2,1,0} %copy.13, f32[]{:T(128)} %d), '
        'kind=kLoop, calls=%fused_computation.18')


@pytest.mark.parametrize("hlo, name, cat", [
    (KERNEL, "fcnn_layer_dgrad", "kernel"),
    (XENT, "softmax_xent_fwd", "kernel"),
    (GATHER, "all-gather-start", "collective"),
    (DONE, "all-gather-done", "collective"),
    (COPY, "copy-start", "xla"),
    (ADAM, "multiply_subtract_fusion", "xla"),
])
def test_name_and_category(hlo, name, cat):
    assert (T.op_name(hlo), T.category(hlo)) == (name, cat)


def test_union_and_overlap():
    u = T.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert T.length(u) == 7
    assert T.overlap(u, [(2, 6), (8, 20)]) == 1 + 1 + 1


def _hand_trace():
    # device 0: kernel 0-10, gather 8-20 (exposed 10-20), adam 25-30
    # device 1: kernel 0-4, gather 2-6 (exposed 4-6)
    # host: batch 0-12, dispatch 12-22, wait 22-40
    dev0 = [(0, 10, KERNEL), (8, 12, GATHER), (25, 5, ADAM)]
    dev1 = [(0, 4, KERNEL), (2, 4, GATHER)]
    spans = [("batch", 0, 12), ("dispatch", 12, 10), ("wait", 22, 18)]
    return T.from_events({"/device:TPU:1": dev1, "/device:TPU:0": dev0},
                         spans)


def test_reduce_hand_trace():
    s = T.reduce(_hand_trace())
    assert s.window_ns == 40
    d0, d1 = s.devices["/device:TPU:0"], s.devices["/device:TPU:1"]
    assert (d0.busy_ns, d0.kernel_ns, d0.collective_ns, d0.xla_ns) == (
        25, 10, 12, 5)
    assert d0.collective_exposed_ns == 10
    assert (d1.busy_ns, d1.collective_exposed_ns) == (6, 2)
    # device 0 idles 20-25 (dispatch 20-22, wait 22-25) and 30-40 (wait)
    assert d0.gaps == (("wait", 10), ("wait", 5))
    assert d1.gaps == (("wait", 34),)
    assert s.mean("busy_ns") == 15.5
    b = s.breakdown()
    assert b["idle_gaps"][0] == ["wait", 34e-9]
    assert dict(b["device_ops"])["fcnn_layer_dgrad"] == pytest.approx(7e-9)


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        T.reduce(T.from_events({}, [("batch", 0, 1)]))


@pytest.fixture(scope="module")
def nn1_trace(tmp_path_factory):
    """Ten NN1 batch-128 training steps traced on one TPU v5e with the
    benchmark's host spans."""
    raw = gzip.decompress((DATA / "nn1.b128.10steps.xplane.pb.gz")
                          .read_bytes())
    path = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    path.write_bytes(raw)
    return T.load_xplane(path)


def test_recorded_trace_planes_and_spans(nn1_trace):
    assert list(nn1_trace.devices) == ["/device:TPU:0"]
    names = [s.name for s in nn1_trace.spans]
    assert (names.count("batch"), names.count("dispatch"),
            names.count("wait")) == (10, 10, 1)


def test_recorded_trace_kernels(nn1_trace):
    """Ten kernel calls a step, as many as the work counter lists: the
    input layer's dgrad is never run."""
    ops = nn1_trace.devices["/device:TPU:0"]
    kernels = [o.name for o in ops if o.category == "kernel"]
    cell = spec.load_cell("nn1.b128")
    calls = spec.load_kind(cell).kernel_calls(cell)
    assert len(kernels) == 10 * len(calls)
    assert set(kernels) == {c.kernel for c in calls}
    assert not [o for o in ops if o.category == "collective"]


def test_recorded_trace_reduction(nn1_trace):
    s = T.reduce(nn1_trace)
    d = s.devices["/device:TPU:0"]
    ops = nn1_trace.devices["/device:TPU:0"]
    assert d.kernel_ns + d.xla_ns + d.collective_ns == sum(
        o.end_ns - o.start_ns for o in ops)
    # ops on the XLA Ops line do not overlap: busy is their sum
    assert (s.window_ns, d.busy_ns, d.kernel_ns, d.xla_ns) == (
        14339299.0, 969258.0, 432951.0, 536307.0)
    assert d.collective_exposed_ns == 0
    # the host-bound step: the longest gaps wait on the host's batch
    assert [g[0] for g in s.breakdown()["idle_gaps"][:3]] == ["batch"] * 3
