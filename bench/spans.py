"""The program's host spans in a profiler trace, beside the benchmark's.

:mod:`tracing` keeps the host spans the benchmark writes (``bench.``).
The program writes its own with ``jax.profiler.TraceAnnotation``
(``repro.fit``, ``repro.predict`` and their children, README
"Profiles"), on the same clock.  :func:`load` keeps both, and
:func:`reduce` gives a :class:`SpanReduction`: the same device numbers
as :func:`tracing.reduce`, plus

- ``span_seconds(name)`` and ``span_count(name)``: the total duration
  and the count of the spans of one name inside the window;
- idle gaps labelled by a chain of spans: the span that overlaps the
  gap most (the longer one on a tie), then, inside it, each time the
  child span that overlaps the gap most, joined with ``>``, such as
  ``bench.request>repro.predict>repro.predict.input``.  A trace with
  only ``bench.`` spans and no ties gets the labels :mod:`tracing`
  gives.

:func:`load` reads a trace file as :func:`tracing.load` does, with the
program's spans kept.  The harness does not use this module yet.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import tracing

SPAN_PREFIXES = ("bench.", "repro.")


def load(path: str) -> tracing.Trace:
    """Device operations and the ``bench.`` and ``repro.`` host spans of
    one trace file."""
    space = tracing.read_xspace(path)
    devices: dict[str, tracing.DeviceOps] = {}
    spans: list[tracing.Span] = []
    for plane in space.planes:
        if tracing._is_device_plane(plane.name):
            ops = tracing._device_ops(plane)
            if ops is not None:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            names = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for ev in line.events:
                    name = names.get(ev.metadata_id, "")
                    if name.startswith(SPAN_PREFIXES):
                        s = line.timestamp_ns + ev.offset_ps / 1e3
                        spans.append(tracing.Span(
                            s, s + ev.duration_ps / 1e3, name))
    return tracing.Trace(devices, sorted(spans, key=lambda s: s.start))


@dataclasses.dataclass
class SpanReduction(tracing.Reduction):
    """A :class:`tracing.Reduction` with the host spans of its window."""
    spans: list = dataclasses.field(repr=False, default_factory=list)

    def span_seconds(self, name: str) -> float:
        """Total seconds of the spans named ``name`` in the window."""
        return sum(s.end - s.start for s in self.spans
                   if s.name == name) / 1e9

    def span_count(self, name: str) -> int:
        return sum(s.name == name for s in self.spans)


def _overlap(s: float, e: float, span: tracing.Span) -> float:
    return min(e, span.end) - max(s, span.start)


def chain(s: float, e: float, spans: list[tracing.Span]) -> str:
    """The chain label of the gap ``[s, e)`` among ``spans``."""
    names, inside = [], None
    while True:
        best, key = None, (0.0, 0.0)
        for sp in spans:
            if inside is not None and (
                    sp is inside or sp.start < inside.start
                    or sp.end > inside.end):
                continue
            k = (_overlap(s, e, sp), sp.end - sp.start)
            if k[0] > 0 and k > key:
                best, key = sp, k
        if best is None:
            return ">".join(names) or "no span"
        names.append(best.name)
        inside = best


def reduce(trace: tracing.Trace, *, top: int = 10) -> SpanReduction:
    """Reduce ``trace`` over its ``bench.window`` span (see the module
    docstring)."""
    bench_only = tracing.Trace(
        trace.devices,
        [s for s in trace.spans if s.name.startswith(tracing.SPAN_PREFIX)])
    red = tracing.reduce(bench_only, top=top)
    window = next(s for s in trace.spans if s.name == tracing.WINDOW_SPAN)
    w0, w1 = window.start, window.end
    gap_s, gap_e = [], []
    for ops in red._ops:
        s, e = tracing._union(ops.start, ops.end)
        gap_s.append(np.r_[w0, e])
        gap_e.append(np.r_[s, w1])
    gap_s = np.concatenate(gap_s) if gap_s else np.zeros(0)
    gap_e = np.concatenate(gap_e) if gap_e else np.zeros(0)
    host = [s for s in trace.spans if s.name != tracing.WINDOW_SPAN]
    inside = [tracing.Span(max(s.start, w0), min(s.end, w1), s.name)
              for s in host if s.end > w0 and s.start < w1]
    longest = np.argsort(gap_s - gap_e, kind="stable")[:top]
    gaps = []
    for s, e in zip(gap_s[longest], gap_e[longest]):
        if e <= s:
            break
        gaps.append([chain(s, e, host), float(e - s) / 1e9])
    fields = {f.name: getattr(red, f.name)
              for f in dataclasses.fields(tracing.Reduction)}
    fields["idle_gaps"] = gaps
    return SpanReduction(**fields, spans=inside)

