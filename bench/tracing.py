"""Reduction from a profiler trace to per-layer device time.

``jax.profiler`` writes an ``.xplane.pb``, an ``XSpace`` protocol
buffer.  :func:`load` parses it with a schema of the fields read here
(:func:`_xspace_class`), into the device operations of each device
plane (``/device:TPU:<n>``, its ``XLA Ops`` line) and the host spans the
benchmark writes with ``jax.profiler.TraceAnnotation`` (names starting
``bench.``).  Both are on the profiler's one clock.  An operation's name
stack is the ``tf_op`` stat of its event metadata, where the compiler
puts the ``jax.named_scope`` stack; ``jax.profiler.ProfileData`` shows
only the events' own stats, which lack it.

On a window (the ``bench.window`` span):

- control flow (``while``, ``conditional``, ``call``) only encloses
  other operations and is left out;
- a layer's device time is the union of the intervals of the operations
  whose name stack holds a component starting with the layer's scope
  prefix (``repro.hist_levels`` matches ``repro.hist_levels[packed]``
  and ``repro.hist_levels_left[pallas]``), averaged over the devices;
- busy time is the union of all operations' intervals, averaged over
  the devices; the idle share is 1 minus busy over the window;
- each idle gap is attributed to the host span that overlaps it most.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import gzip
import os
import re

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
STACK_STAT = "tf_op"
CATEGORY_STAT = "hlo_category"
CONTROL = ("while", "conditional", "call")
_WRAPPERS = ("jit(", "pjit(", "while", "body", "cond", "closed_call",
             "checkpoint")


@dataclasses.dataclass(frozen=True)
class Op:
    """One operation as a hand-made trace gives it."""
    start: float            # ns
    end: float              # ns
    name: str               # the HLO op's short name
    stack: str = ""         # its name stack ("" where the trace has none)
    category: str = ""      # its HLO category


@dataclasses.dataclass(frozen=True)
class OpInfo:
    name: str
    stack: str
    category: str

    @property
    def control(self) -> bool:
        return (self.category in CONTROL
                or self.name.split(".")[0] in CONTROL)


@dataclasses.dataclass
class DeviceOps:
    """One device's operations: intervals in ns and an index into
    ``info`` for each."""
    start: np.ndarray
    end: np.ndarray
    kind: np.ndarray
    info: list[OpInfo]

    @classmethod
    def of(cls, ops: list[Op]) -> "DeviceOps":
        info = sorted({OpInfo(o.name, o.stack, o.category) for o in ops},
                      key=lambda i: (i.name, i.stack))
        index = {i: k for k, i in enumerate(info)}
        return cls(np.array([o.start for o in ops], np.float64),
                   np.array([o.end for o in ops], np.float64),
                   np.array([index[OpInfo(o.name, o.stack, o.category)]
                             for o in ops], np.int64), info)

    def select(self, mask: np.ndarray) -> "DeviceOps":
        return DeviceOps(self.start[mask], self.end[mask], self.kind[mask],
                         self.info)


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    devices: dict[str, DeviceOps]
    spans: list[Span]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


@functools.cache
def _xspace_class():
    """The ``XSpace`` message class, built from the fields of
    ``tsl/profiler/protobuf/xplane.proto`` that :func:`load` reads, in a
    pool of its own; a parse skips the other fields."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    schema = {
        "XSpace": [("planes", 1, many, "XPlane")],
        "XPlane": [("name", 2, one, F.TYPE_STRING),
                   ("lines", 3, many, "XLine"),
                   ("event_metadata", 4, many, "EventMetadataEntry"),
                   ("stat_metadata", 5, many, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, one, F.TYPE_INT64),
                               ("value", 2, one, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, one, F.TYPE_INT64),
                              ("value", 2, one, "XStatMetadata")],
        "XLine": [("name", 2, one, F.TYPE_STRING),
                  ("timestamp_ns", 3, one, F.TYPE_INT64),
                  ("events", 4, many, "XEvent")],
        "XEvent": [("metadata_id", 1, one, F.TYPE_INT64),
                   ("offset_ps", 2, one, F.TYPE_INT64),
                   ("duration_ps", 3, one, F.TYPE_INT64)],
        "XStat": [("metadata_id", 1, one, F.TYPE_INT64),
                  ("str_value", 5, one, F.TYPE_STRING),
                  ("ref_value", 7, one, F.TYPE_UINT64)],
        "XEventMetadata": [("name", 2, one, F.TYPE_STRING),
                           ("display_name", 4, one, F.TYPE_STRING),
                           ("stats", 5, many, "XStat")],
        "XStatMetadata": [("name", 2, one, F.TYPE_STRING)],
    }
    proto = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in schema.items():
        m = proto.message_type.add(name=msg)
        for name, number, label, typ in fields:
            f = m.field.add(name=name, number=number, label=label)
            if isinstance(typ, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_xspace(path: str):
    """Parse an ``.xplane.pb`` (or a gzipped one, ``.gz``)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        space = _xspace_class()()
        space.ParseFromString(fh.read())
    return space


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def _infos(plane) -> dict[int, OpInfo]:
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}

    def value(stat):
        if stat.str_value:
            return stat.str_value
        return stat_names.get(stat.ref_value, "") if stat.ref_value else ""

    out = {}
    for entry in plane.event_metadata:
        md = entry.value
        stats = {stat_names.get(s.metadata_id): value(s) for s in md.stats}
        out[entry.key] = OpInfo(md.display_name or md.name,
                                stats.get(STACK_STAT, "").rstrip(":"),
                                stats.get(CATEGORY_STAT, ""))
    return out


def _device_ops(plane) -> DeviceOps | None:
    infos = _infos(plane)
    rows = []
    for line in plane.lines:
        if line.name == OPS_LINE:
            base = line.timestamp_ns
            rows += [(base, e.offset_ps, e.duration_ps, e.metadata_id)
                     for e in line.events]
    if not rows:
        return None
    a = np.array(rows, np.int64)
    start = a[:, 0] + a[:, 1] / 1e3
    ids, kind = np.unique(a[:, 3], return_inverse=True)
    info = [infos.get(int(i), OpInfo(str(i), "", "")) for i in ids]
    order = np.argsort(start, kind="stable")
    return DeviceOps(start[order], (start + a[:, 2] / 1e3)[order],
                     kind.reshape(-1)[order], info)


def load(path: str) -> Trace:
    """Device operations and ``bench.`` host spans of one trace file."""
    space = read_xspace(path)
    devices: dict[str, DeviceOps] = {}
    spans: list[Span] = []
    for plane in space.planes:
        if _is_device_plane(plane.name):
            ops = _device_ops(plane)
            if ops is not None:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            names = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for ev in line.events:
                    name = names.get(ev.metadata_id, "")
                    if name.startswith(SPAN_PREFIX):
                        s = line.timestamp_ns + ev.offset_ps / 1e3
                        spans.append(Span(s, s + ev.duration_ps / 1e3, name))
    return Trace(devices, sorted(spans, key=lambda s: s.start))


def _union(start: np.ndarray, end: np.ndarray):
    """The union of intervals, as sorted disjoint (starts, ends)."""
    if not len(start):
        return start, end
    o = np.argsort(start, kind="stable")
    s, e = start[o], end[o]
    reach = np.maximum.accumulate(e)
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(s) - 1]
    return s[first], reach[last]


def _label(info: OpInfo) -> str:
    """A stable name for a device operation: its name stack without the
    jit and loop wrappers, else its HLO name without the numeric id."""
    parts = [p for p in info.stack.split("/")
             if p and not p.startswith(_WRAPPERS)]
    if parts:
        return "/".join(parts)
    return re.sub(r"[.\d]+$", "", info.name) or info.name


@dataclasses.dataclass
class Reduction:
    """One trace reduced over one window."""
    window_s: float
    busy_s: float                          # averaged over devices
    n_devices: int
    top_ops: list[list]                    # [[label, seconds], ...]
    idle_gaps: list[list]                  # [[host span, seconds], ...]
    _ops: list[DeviceOps] = dataclasses.field(repr=False,
                                              default_factory=list)

    def scope_seconds(self, prefix: str) -> float:
        """Device seconds in which an operation under scope ``prefix``
        ran, averaged over the devices."""
        if not self.n_devices:
            return 0.0
        total = 0.0
        for ops in self._ops:
            match = np.array([any(p.startswith(prefix)
                                  for p in i.stack.split("/"))
                              for i in ops.info] + [False])
            sel = match[ops.kind] if len(ops.kind) else match[:0]
            s, e = _union(ops.start[sel], ops.end[sel])
            total += float(np.sum(e - s))
        return total / 1e9 / self.n_devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0


def reduce(trace: Trace, *, top: int = 10) -> Reduction:
    """Reduce ``trace`` over its ``bench.window`` span."""
    windows = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0].start, windows[0].end
    kept, busy, gap_s, gap_e = [], 0.0, [], []
    by_label = collections.Counter()
    for ops in trace.devices.values():
        control = np.array([i.control for i in ops.info] + [False])
        inside = (ops.end > w0) & (ops.start < w1) & ~control[ops.kind]
        ops = ops.select(inside)
        ops.start = np.maximum(ops.start, w0)
        ops.end = np.minimum(ops.end, w1)
        kept.append(ops)
        s, e = _union(ops.start, ops.end)
        busy += float(np.sum(e - s))
        gap_s.append(np.r_[w0, e])
        gap_e.append(np.r_[s, w1])
        per_kind = np.bincount(ops.kind, ops.end - ops.start,
                               minlength=len(ops.info))
        for info, t in zip(ops.info, per_kind):
            if t:
                by_label[_label(info)] += float(t)
    n_dev = len(trace.devices)
    host = [s for s in trace.spans if s.name != WINDOW_SPAN]
    gap_s = np.concatenate(gap_s) if gap_s else np.zeros(0)
    gap_e = np.concatenate(gap_e) if gap_e else np.zeros(0)
    longest = np.argsort(gap_s - gap_e, kind="stable")[:top]
    gap_rows = []
    for s, e in zip(gap_s[longest], gap_e[longest]):
        if e <= s:
            break
        best, name = 0.0, "no span"
        for sp in host:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > best:
                best, name = ov, sp.name
        gap_rows.append([name, float(e - s) / 1e9])
    scale = 1e9 * max(n_dev, 1)
    return Reduction(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / scale,
        n_devices=n_dev,
        top_ops=[[k, v / scale] for k, v in by_label.most_common(top)],
        idle_gaps=gap_rows,
        _ops=kept)
