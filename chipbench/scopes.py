"""Split a traced window's device time by the program's own scopes, and
read the program's own host spans.

The program (``repro.obs``) names its work: host spans ``repro.*`` on the
profiler's clock (``repro.batch.index``, ``repro.batch.put``) and
``jax.named_scope``s on every op of the training step (``fp<i>.run``,
``fp<i>.xfer``, ``loss``, ``grad_norm``, ``optimizer``, ``pad``).  A device
op's scope path is the ``tf_op`` stat of its event metadata on its
``/device:TPU:<n>`` plane, e.g. ``jit(step)/transpose(jvp())/fp2.run/
jit(fcnn_layer_dgrad)/pad/jit(_pad)/pad``.  ``jax.profiler.ProfileData``
does not expose event metadata stats, so they are read here from the
``XSpace`` protobuf's wire format with the standard library, and joined to
the ``ProfileData`` events by metadata name (the op's HLO text).

An op's class is the first of ``pad``, ``optimizer``, ``grad_norm``,
``xfer`` (any ``fp<i>.xfer``) and ``program`` (any other program scope)
that its scope path names, else ``none``.  Only XLA and collective ops are
classed; kernels are ``chipbench.trace``'s.  A profile of a program without
these names reads ``None`` for every value.

    PYTHONPATH=src python3 -m chipbench.scopes <xplane.pb[.gz]> --steps N

prints the split of one profile as JSON.
"""

from __future__ import annotations

import collections
import dataclasses
import gzip
import json
import re
import struct
from pathlib import Path

from chipbench import trace as tracing

SPAN_PREFIX = "repro."
BATCH_PUT = "repro.batch.put"
CLASSES = ("pad", "optimizer", "grad_norm", "xfer", "program", "none")
# the name each class's device time goes by in ``split``
CLASS_METRICS = {"pad": "pad_ms", "optimizer": "optimizer_ms",
                 "grad_norm": "grad_norm_ms", "xfer": "transfer_ms",
                 "program": "program_ms", "none": "unscoped_ms"}
MODULES_LINE = "XLA Modules"

_TRANSFORM = re.compile(r"^[\w-]+\((.*)\)$")
_XFER = re.compile(r"^fp\d+\.xfer$")
_OTHER = re.compile(r"^(?:fp\d+\.run|loss)$")


# ------------------------------------------------------------ wire format


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """``(field number, value)`` of one protobuf message: an int for
    varint and fixed fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value = struct.unpack_from("<q", buf, i)[0]
            i += 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 5:
            value = struct.unpack_from("<i", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _map_values(entries: list[bytes]) -> list[bytes]:
    """The values (field 2) of protobuf map entries."""
    return [v for e in entries for k, v in _fields(e) if k == 2]


def _plane_tf_ops(plane: bytes) -> tuple[str, dict[str, str]]:
    """(plane name, event metadata name -> ``tf_op``) of one ``XPlane``.

    XPlane: name = 2, event_metadata = 4 (map id -> XEventMetadata),
    stat_metadata = 5 (map id -> XStatMetadata).  XEventMetadata: name = 2,
    stats = 5.  XStatMetadata: id = 1, name = 2.  XStat: metadata_id = 1,
    str_value = 5, ref_value = 7 (the id of a stat metadata whose name is
    the string)."""
    name, events, stats = "", [], []
    for number, value in _fields(plane):
        if number == 2:
            name = value.decode()
        elif number == 4:
            events.append(value)
        elif number == 5:
            stats.append(value)
    if not name.startswith(tracing.DEVICE_PREFIX):
        return name, {}
    stat_names: dict[int, str] = {}
    for meta in _map_values(stats):
        f = dict(_fields(meta))
        stat_names[f.get(1, 0)] = f.get(2, b"").decode()
    tf_op = next((i for i, s in stat_names.items() if s == "tf_op"), None)
    out: dict[str, str] = {}
    for meta in _map_values(events):
        ev_name, scope = "", ""
        for number, value in _fields(meta):
            if number == 2:
                ev_name = value.decode()
            elif number == 5 and tf_op is not None:
                stat = dict(_fields(value))
                if stat.get(1) == tf_op:
                    scope = (stat[5].decode() if 5 in stat
                             else stat_names.get(stat.get(7, -1), ""))
        # ``tf_op`` reads "<op name>:<op type>"; the type is empty here
        out[ev_name] = scope.rpartition(":")[0] if ":" in scope else scope
    return name, out


def tf_ops(raw: bytes) -> dict[str, dict[str, str]]:
    """Per ``/device:TPU:<n>`` plane, each op's HLO text -> its ``tf_op``
    (``""`` where the op carries none), from a serialized ``XSpace``
    (planes = 1)."""
    out = {}
    for number, value in _fields(raw):
        if number == 1:
            name, ops = _plane_tf_ops(value)
            if ops:
                out[name] = ops
    return out


# ---------------------------------------------------------------- profile


@dataclasses.dataclass(frozen=True)
class ScopedOp:
    start_ns: float
    end_ns: float
    name: str
    category: str
    scope: str


@dataclasses.dataclass(frozen=True)
class Profile:
    """A traced window: the benchmark's ``Trace`` (what ``reduce`` reads),
    every device op with its scope path, the program's ``repro.*`` host
    spans, and each device's ``XLA Modules`` (one per executed step)."""

    trace: tracing.Trace
    ops: dict[str, list[ScopedOp]]
    program_spans: list[tracing.Span]
    modules: dict[str, list[tuple[float, float]]]


def read_raw(path: str | Path) -> bytes:
    raw = Path(path).read_bytes()
    return gzip.decompress(raw) if raw[:2] == b"\x1f\x8b" else raw


def load(path: str | Path) -> Profile:
    """Everything this module reads from one ``.xplane.pb`` (or its
    gzip)."""
    from jax.profiler import ProfileData
    raw = read_raw(path)
    scopes = tf_ops(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    devices: dict[str, list] = {}
    modules: dict[str, list] = {}
    host, program = [], []
    for plane in pd.planes:
        if plane.name.startswith(tracing.DEVICE_PREFIX):
            for line in plane.lines:
                events = [(e.start_ns, e.duration_ns, e.name)
                          for e in line.events]
                if line.name == tracing.OPS_LINE:
                    devices[plane.name] = events
                elif line.name == MODULES_LINE:
                    modules[plane.name] = sorted((s, s + d)
                                                 for s, d, _ in events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in tracing.HOST_SPANS:
                        host.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith(SPAN_PREFIX):
                        program.append(tracing.Span(
                            e.name, e.start_ns, e.start_ns + e.duration_ns))
    scoped = {plane: [(s, d, h, scopes.get(plane, {}).get(h, ""))
                      for s, d, h in events]
              for plane, events in devices.items()}
    return from_events(scoped, host, program, modules)


def from_events(devices: dict[str, list[tuple[float, float, str, str]]],
                spans: list[tuple[str, float, float]],
                program_spans: list[tracing.Span],
                modules: dict[str, list[tuple[float, float]]] | None = None
                ) -> Profile:
    """A Profile from ``(start_ns, duration_ns, hlo, tf_op)`` per device,
    the benchmark's ``(name, start_ns, duration_ns)`` host spans and the
    program's spans."""
    ops = {plane: sorted((ScopedOp(s, s + d, tracing.op_name(h),
                                   tracing.category(h), scope)
                          for s, d, h, scope in events),
                         key=lambda o: o.start_ns)
           for plane, events in devices.items()}
    trace = tracing.from_events(
        {plane: [e[:3] for e in events] for plane, events in devices.items()},
        spans)
    return Profile(trace=trace, ops=ops,
                   program_spans=sorted(program_spans,
                                        key=lambda s: s.start_ns),
                   modules=modules or {})


# ----------------------------------------------------------------- reading


def scope_names(tf_op: str) -> list[str]:
    """The scopes on an op's path, outermost first, with transforms
    unwrapped (``transpose(jvp(fp1.run))`` -> ``fp1.run``).  The path's
    last component is the op itself, and ``jit(<function>)`` a call: neither
    is a scope."""
    out = []
    for part in tf_op.split("/")[:-1]:
        if part.startswith("jit("):
            continue
        while (m := _TRANSFORM.match(part)):
            part = m.group(1)
        if part:
            out.append(part)
    return out


def op_class(tf_op: str) -> str:
    names = scope_names(tf_op)
    for cls in ("pad", "optimizer", "grad_norm"):
        if cls in names:
            return cls
    if any(_XFER.match(n) for n in names):
        return "xfer"
    if any(_OTHER.match(n) for n in names):
        return "program"
    return "none"


def class_ns(profile: Profile) -> dict[str, float]:
    """Nanoseconds of XLA and collective ops per class, mean over the
    chips."""
    total: collections.Counter = collections.Counter()
    for ops in profile.ops.values():
        for o in ops:
            if o.category != "kernel":
                total[op_class(o.scope)] += o.end_ns - o.start_ns
    n = max(len(profile.ops), 1)
    return {c: total[c] / n for c in CLASSES}


def _scoped(profile: Profile) -> bool:
    return any(op_class(o.scope) != "none"
               for ops in profile.ops.values() for o in ops)


def class_ms(profile: Profile, cls: str, steps: int) -> float | None:
    """Device ms per step of class ``cls``; None where the profile holds
    no program scope."""
    if not _scoped(profile):
        return None
    return class_ns(profile)[cls] / steps / 1e6


def _put_spans(profile: Profile) -> list[tracing.Span]:
    return [s for s in profile.program_spans if s.name == BATCH_PUT]


def batch_put_us(profile: Profile) -> float | None:
    """Mean host µs of the ``repro.batch.put`` span (one per step)."""
    puts = _put_spans(profile)
    if not puts:
        return None
    return sum(s.end_ns - s.start_ns for s in puts) / len(puts) / 1e3


def window(t: tracing.Trace) -> tuple[float, float]:
    """``tracing.reduce``'s window: first host span or device op to the
    last."""
    starts = [s.start_ns for s in t.spans]
    ends = [s.end_ns for s in t.spans]
    for ops in t.devices.values():
        if ops:
            starts.append(ops[0].start_ns)
            ends.append(max(o.end_ns for o in ops))
    return min(starts), max(ends)


def idle_put_pct(profile: Profile) -> float | None:
    """Percent of device idle time in the window that overlaps a
    ``repro.batch.put`` span, mean over the chips."""
    puts = tracing.union((s.start_ns, s.end_ns) for s in _put_spans(profile))
    if not puts or not profile.trace.devices:
        return None
    lo, hi = window(profile.trace)
    shares = []
    for ops in profile.trace.devices.values():
        busy = tracing.union((o.start_ns, o.end_ns) for o in ops)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle_ns = tracing.length(idle)
        if idle_ns > 0:
            shares.append(100.0 * tracing.overlap(idle, puts) / idle_ns)
    return sum(shares) / len(shares) if shares else None


def dispatch_lead_ns(profile: Profile) -> float | None:
    """Smallest gap from the start of a step's ``dispatch`` span to the
    start of that step (the k-th ``XLA Modules`` event answers the k-th
    dispatch), over every chip: a negative value is how far the device
    clock runs ahead of the host's."""
    dispatch = [s.start_ns for s in profile.trace.spans
                if s.name == "dispatch"]
    gaps = [m[0] - d for mods in profile.modules.values()
            for d, m in zip(dispatch, mods)]
    return min(gaps) if gaps else None


def split(profile: Profile, steps: int) -> dict[str, float | None]:
    """Every value this module reads, per step."""
    out: dict[str, float | None] = {
        CLASS_METRICS[c]: class_ms(profile, c, steps) for c in CLASSES}
    out["batch_put_us"] = batch_put_us(profile)
    out["idle_put_pct"] = idle_put_pct(profile)
    out["dispatch_lead_us"] = (None if (d := dispatch_lead_ns(profile))
                               is None else d / 1e3)
    summary = tracing.reduce(profile.trace)
    out["xla_ops_ms"] = summary.mean("xla_ns") / steps / 1e6
    out["window_s"] = summary.window_ns / 1e9
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.scopes",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("profile", help=".xplane.pb or .xplane.pb.gz")
    ap.add_argument("--steps", type=int, required=True,
                    help="training steps in the traced window")
    args = ap.parse_args(argv)
    print(json.dumps(split(load(args.profile), args.steps)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
