"""The split of device time by the program's own scopes and the reading of
its host spans (``chipbench/scopes.py``): the ``XSpace`` wire-format
reader on the traces recorded on the chip, and each value on hand-made
profiles."""

from pathlib import Path

import pytest

from chipbench import harness, spec
from chipbench import scopes as S
from chipbench import trace as T

DATA = Path(__file__).resolve().parents[1] / "testdata"
PLAIN = DATA / "nn1.b128.10steps.xplane.pb.gz"

FUSION = ('%multiply_subtract_fusion.2 = f32[1,500,10]{2,1,0} fusion(f32[1,'
          '500,10]{2,1,0} %a, f32[]{:T(128)} %d), kind=kLoop, calls=%f.18')
PAD = ('%pad.7 = f32[128,896]{1,0} pad(f32[128,784]{1,0} %x, f32[] %z), '
       'padding=0_0x0_112')
COPY = ('%copy-done.3 = f32[1,500,10]{2,1,0} copy-done((f32[1,500,10]{2,1,0}'
        ', f32[1,500,10]{2,1,0}, u32[]) %copy-start.3)')
GATHER = ('%all-gather-start.2 = (f32[1,128,1000]{2,1,0}, f32[4,128,1000]'
          '{2,1,0}) all-gather-start(f32[1,128,1000]{2,1,0} %y), dimensions'
          '={0}')
KERNEL = ('%fcnn_layer.4 = f32[128,512]{1,0} custom-call(f32[128,896]{1,0} '
          '%a), custom_call_target="tpu_custom_call"')

BWD_PAD = "jit(step)/transpose(jvp())/fp2.run/jit(fcnn_layer_dgrad)/pad/slice"


@pytest.mark.parametrize("tf_op, cls", [
    ("jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/grad_norm/reduce_sum", "grad_norm"),
    ("jit(step)/jvp()/fp1.run/jit(fcnn_layer)/pad/jit(_pad)/pad", "pad"),
    (BWD_PAD, "pad"),
    ("jit(step)/transpose(jvp(fp3.xfer))/scatter-add", "xfer"),
    ("jit(step)/jvp()/shard_map/fp3.xfer/all_gather", "xfer"),
    ("jit(step)/jvp()/fp2.run/jit(fcnn_layer)/pallas_call", "program"),
    ("jit(step)/transpose(jvp(loss))/mul", "program"),
    # the op itself and a jitted call name no scope
    ("jit(step)/jvp()/jit(fcnn_layer)/jit(_pad)/pad", "none"),
    ("jit(pad)/mul", "none"),
    ("state['params']['layers'][2]['w']", "none"),
    ("", "none"),
])
def test_op_class(tf_op, cls):
    assert S.op_class(tf_op) == cls


def test_scope_names_unwrap_transforms():
    assert S.scope_names("jit(step)/transpose(jvp(fp1.run))/pad/slice") == [
        "fp1.run", "pad"]


def _hand_profile(scoped: bool = True):
    # device 0: kernel 0-10 (run), adam 10-14, pad 14-15, gather 15-17,
    #           copy 17-18, grad norm 30-31; idle 18-30 and 31-40
    # device 1: pad 0-2 (bwd), idle 2-40
    # host: dispatch 0-20, wait 20-40; put spans 5-25 and 35-45
    def scope(s):
        return s if scoped else ""

    dev0 = [(0, 10, KERNEL, scope("jit(step)/jvp()/fp1.run/pallas_call")),
            (10, 4, FUSION, scope("jit(step)/optimizer/sub")),
            (14, 1, PAD, scope("jit(step)/jvp()/fp1.run/pad/jit(_pad)/pad")),
            (15, 2, GATHER, scope("jit(step)/jvp()/fp1.xfer/all_gather")),
            (17, 1, COPY, ""),
            (30, 1, FUSION, scope("jit(step)/grad_norm/reduce_sum"))]
    dev1 = [(0, 2, PAD, scope(BWD_PAD))]
    host = [("dispatch", 0, 20), ("wait", 20, 20)]
    program = ([T.Span("repro.batch.index", 3, 5),
                T.Span("repro.batch.put", 5, 25),
                T.Span("repro.batch.put", 35, 45)] if scoped else [])
    modules = {"/device:TPU:0": [(0, 18)], "/device:TPU:1": [(0, 2)]}
    return S.from_events({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                         host, program, modules)


def test_class_split_on_a_hand_profile():
    p = _hand_profile()
    # kernels are left out; per class, mean over the two chips
    assert S.class_ns(p) == {"pad": (1 + 2) / 2, "optimizer": 4 / 2,
                             "grad_norm": 1 / 2, "xfer": 2 / 2,
                             "program": 0.0, "none": 1 / 2}
    out = S.split(p, steps=1)
    assert out["optimizer_ms"] == pytest.approx(2e-6)
    assert out["pad_ms"] == pytest.approx(1.5e-6)
    assert out["transfer_ms"] == pytest.approx(1e-6)
    assert out["unscoped_ms"] == pytest.approx(0.5e-6)
    # the classes and the kernels make up every op's time
    summary = T.reduce(p.trace)
    assert sum(S.class_ns(p).values()) == pytest.approx(
        summary.mean("xla_ns") + summary.mean("collective_ns"))


def test_program_spans_on_a_hand_profile():
    p = _hand_profile()
    # two put spans of 20 and 10 ns
    assert S.batch_put_us(p) == pytest.approx(15e-3)
    # window 0-45 (the last put span is no benchmark span: the window ends
    # at 40).  Device 0 idles 18-30 and 31-40 (21 ns), of which puts cover
    # 18-25 and 35-40: 12 / 21.  Device 1 idles 2-40 (38 ns), of which puts
    # cover 5-25 and 35-40: 25 / 38.
    assert S.window(p.trace) == (0, 40)
    assert S.idle_put_pct(p) == pytest.approx(
        100 * (12 / 21 + 25 / 38) / 2)
    # the step's dispatch starts at 0 and both devices start it at 0
    assert S.dispatch_lead_ns(p) == 0


def test_a_profile_without_program_names_reads_none():
    out = S.split(_hand_profile(scoped=False), steps=1)
    for key in S.CLASS_METRICS.values():
        assert out[key] is None
    assert out["batch_put_us"] is None and out["idle_put_pct"] is None


def test_tf_ops_of_the_recorded_trace():
    """The trace recorded before the program named its work: each device
    op's ``tf_op`` is the JAX op name XLA kept."""
    ops = S.tf_ops(S.read_raw(PLAIN))
    assert list(ops) == ["/device:TPU:0"]
    by_name: dict[str, set] = {}
    for hlo, tf_op in ops["/device:TPU:0"].items():
        by_name.setdefault(T.op_name(hlo), set()).add(tf_op)
    assert by_name["multiply_subtract_fusion"] == {"jit(step)/sub"}
    assert all(t.endswith("/jit(_pad)/pad") for t in by_name["pad"])
    assert by_name["copy-start"] == {""}


def test_the_recorded_trace_has_no_program_names(tmp_path):
    p = S.load(PLAIN)
    out = S.split(p, steps=10)
    assert all(out[k] is None for k in S.CLASS_METRICS.values())
    assert out["batch_put_us"] is None and out["idle_put_pct"] is None
    # the benchmark's own reading of the same file
    raw = tmp_path / "t.xplane.pb"
    raw.write_bytes(S.read_raw(PLAIN))
    assert p.trace == T.load_xplane(raw)
    assert out["xla_ops_ms"] == 536307.0 / 10 / 1e6
    assert len(p.modules["/device:TPU:0"]) == 10


@pytest.fixture(scope="module")
def scoped():
    """Ten NN1 batch-128 training steps traced on one TPU v5e with the
    benchmark's host spans and the program's spans and scopes."""
    return S.load(DATA / "nn1.b128.10steps.scoped.xplane.pb.gz")


def test_scoped_recorded_trace_values(scoped):
    out = S.split(scoped, steps=10)
    assert out == pytest.approx({
        "pad_ms": 0.0027573, "optimizer_ms": 0.0144536,
        "grad_norm_ms": 0.0027722, "transfer_ms": 0.0,
        "program_ms": 0.0008876, "unscoped_ms": 0.0325949,
        "batch_put_us": 640.423, "idle_put_pct": 51.111209896319956,
        "dispatch_lead_us": -1001.746, "xla_ops_ms": 0.0534656,
        "window_s": 0.013497559})
    # on one chip every non-kernel op is XLA's: the classes are all of it
    assert sum(out[k] for k in S.CLASS_METRICS.values()) == pytest.approx(
        out["xla_ops_ms"])


def test_scoped_recorded_trace_spans(scoped):
    names = [s.name for s in scoped.program_spans]
    assert names.count("repro.batch.index") == 10
    assert names.count("repro.batch.put") == 10
    batch = [s.end_ns - s.start_ns for s in scoped.trace.spans
             if s.name == "batch"]
    # the put is a part of the benchmark's batch span
    assert S.batch_put_us(scoped) < 1e-3 * sum(batch) / len(batch)


def test_scoped_recorded_trace_leaves_only_xla_copies_unscoped(scoped):
    unscoped = {(o.name, o.scope) for ops in scoped.ops.values() for o in ops
                if o.category != "kernel" and S.op_class(o.scope) == "none"}
    assert {name for name, _ in unscoped} <= {"copy", "copy-start",
                                              "copy-done"}
    assert not any(scope.startswith("jit(step)/") for _, scope in unscoped)


@pytest.mark.parametrize("metric", ["optimizer_ms", "pad_ms", "unscoped_ms",
                                    "batch_put_us", "idle_put_pct"])
def test_scope_readers_on_the_recorded_trace(scoped, metric):
    """Each per-layer reader of the program's scopes and spans gives what
    ``split`` reads from the same profile."""
    cell = spec.load_cell("nn1.b128")
    ctx = harness.Context(
        cell=cell, flops_per_sample=0.0, kernel_calls=[], steps=10, host={},
        summary=T.reduce(scoped.trace), profile=scoped, peak={},
        samples_per_s=0.0)
    got = spec.load_reader(cell, metric)(ctx)
    assert got is not None and got == S.split(scoped, steps=10)[metric]
