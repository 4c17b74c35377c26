"""The trace reduction, on hand-made traces and on a trace recorded on a
TPU v5e (``bench/tests/data``: one 65,536-row ``fit`` call and three
1,024-row requests to a 50-tree forest under ``bench.window``, with the
plane of HLO protos taken out)."""

import collections
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import tracing  # noqa: E402
from tracing import DeviceOps, Op, Span, Trace  # noqa: E402

DATA = BENCH / "tests" / "data"
MS = 1e6     # ns


def _trace():
    # window 0-100 ms; device busy 10-30 (hist), 25-40 (split gain,
    # overlapping), 60-70 (an op without a name stack), and 95-120 which
    # the window clips to 95-100
    # the while loop enclosing the first two is control flow: left out
    ops = [Op(10 * MS, 30 * MS, "scatter.1",
              "jit(_fit_scanned)/while/body/repro.hist_levels[packed]/scatter"),
           Op(25 * MS, 40 * MS, "fusion.7",
              "jit(_fit_scanned)/while/body/repro.split_gain[packed]/reduce"),
           Op(5 * MS, 45 * MS, "while.2", "jit(_fit_scanned)/while", "while"),
           Op(60 * MS, 70 * MS, "copy.3", ""),
           Op(95 * MS, 120 * MS, "fusion.9",
              "jit(f)/repro.hist_levels_left[pallas]/add")]
    spans = [Span(0, 100 * MS, "bench.window"),
             Span(40 * MS, 58 * MS, "bench.prepare"),
             Span(58 * MS, 100 * MS, "bench.request")]
    return Trace({"/device:TPU:0": DeviceOps.of(ops)}, spans)


def test_busy_is_the_union_of_op_intervals_in_the_window():
    red = tracing.reduce(_trace())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.045)         # 10-40, 60-70, 95-100
    assert red.idle_share == pytest.approx(0.55)


def test_scope_time_matches_the_prefix_on_any_stack_component():
    red = tracing.reduce(_trace())
    # hist_levels and hist_levels_left: 20 ms + 5 ms clipped
    assert red.scope_seconds("repro.hist_levels") == pytest.approx(0.025)
    assert red.scope_seconds("repro.split_gain") == pytest.approx(0.015)
    assert red.scope_seconds("repro.traverse") == 0.0


def test_gaps_go_to_the_host_span_that_overlaps_them_most():
    red = tracing.reduce(_trace())
    gaps = {round(s * 1e3): name for name, s in red.idle_gaps}
    # 40-60 overlaps prepare (18 ms) more than request (2 ms)
    assert gaps == {10: "no span", 20: "bench.prepare", 25: "bench.request"}
    assert [g[1] for g in red.idle_gaps] == sorted(
        (g[1] for g in red.idle_gaps), reverse=True)


def test_top_ops_are_labelled_by_their_scope():
    red = tracing.reduce(_trace())
    labels = dict(red.top_ops)
    assert labels["repro.hist_levels[packed]/scatter"] == pytest.approx(0.02)
    assert labels["copy"] == pytest.approx(0.01)


def test_devices_are_averaged():
    t = _trace()
    t.devices["/device:TPU:1"] = DeviceOps.of([Op(0, 100 * MS, "fusion")])
    red = tracing.reduce(t)
    assert red.busy_s == pytest.approx((0.045 + 0.1) / 2)


def test_a_trace_without_a_window_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce(Trace({}, [Span(0, 1, "bench.request")]))


def _plane(stats):
    """An XSpace of one device plane whose one operation's metadata has
    ``stats`` ({name: str or ("ref", str)}), with a window around it."""
    space = tracing._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    names = {}
    for k, (name, v) in enumerate(stats.items(), start=1):
        for n in (name,) + ((v[1],) if isinstance(v, tuple) else ()):
            if n not in names:
                names[n] = len(names) + 1
                dev.stat_metadata.add(key=names[n]).value.name = n
    md = dev.event_metadata.add(key=7).value
    md.name, md.display_name = "%fusion.3 = f32[8] fusion(...)", "fusion.3"
    for name, v in stats.items():
        if isinstance(v, tuple):
            md.stats.add(metadata_id=names[name], ref_value=names[v[1]])
        else:
            md.stats.add(metadata_id=names[name], str_value=v)
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    line.events.add(metadata_id=7, offset_ps=2_000_000, duration_ps=3_000_000)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "bench.window"
    host.event_metadata.add(key=2).value.name = "jit_f"
    hl = host.lines.add(name="python3", timestamp_ns=0)
    hl.events.add(metadata_id=1, offset_ps=0, duration_ps=10_000_000)
    hl.events.add(metadata_id=2, offset_ps=1_000_000, duration_ps=1_000_000)
    return space


@pytest.mark.parametrize("stats,want", [
    ({"tf_op": "jit(f)/repro.traverse[packed]/gather:",
      "hlo_category": "custom fusion"}, "jit(f)/repro.traverse[packed]/gather"),
    ({"tf_op": ("ref", "jit(f)/repro.split_gain[ref]"),
      "hlo_category": ("ref", "loop fusion")}, "jit(f)/repro.split_gain[ref]"),
    ({"source": "ops.py:12", "hlo_category": "custom fusion"}, ""),
    ({}, ""),
])
def test_an_ops_stack_comes_from_its_stats(tmp_path, stats, want):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_plane(stats).SerializeToString())
    trace = tracing.load(str(path))
    ops = trace.devices["/device:TPU:0"]
    (info,) = ops.info
    assert info.stack == want and info.name == "fusion.3"
    # the line's timestamp in ns plus the event's offset in ps
    assert ops.start.tolist() == [3000.0] and ops.end.tolist() == [6000.0]
    assert [s.name for s in trace.spans] == ["bench.window"]


CHIP_TRACE = str(DATA / "v5e_fit_predict.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip():
    return tracing.reduce(tracing.load(CHIP_TRACE))


def test_a_chip_trace_has_the_window_and_its_spans():
    trace = tracing.load(CHIP_TRACE)
    assert list(trace.devices) == ["/device:TPU:0"]
    names = collections.Counter(s.name for s in trace.spans)
    assert names == {"bench.window": 1, "bench.fit": 1, "bench.prepare": 3,
                     "bench.request": 3}
    ops = trace.devices["/device:TPU:0"]
    assert len(ops.start) == 1655 and bool(np.all(ops.end >= ops.start))
    assert {i.name.split(".")[0] for i in ops.info if i.control} == {"while"}


@pytest.mark.parametrize("prefix,low,high", [
    ("repro.hist_levels", 0.40, 0.55),      # the scatter: most of the fit
    ("repro.split_gain", 1e-5, 0.01),
    ("repro.bin_features", 1e-6, 0.01),
    ("repro.traverse", 0.005, 0.03),        # three 1,024-row requests
])
def test_a_chip_trace_gives_each_layers_time(chip, prefix, low, high):
    assert low < chip.scope_seconds(prefix) < high


def test_a_chip_trace_busy_lies_inside_the_window(chip):
    assert 0.5 < chip.window_s < 0.65
    assert 0.9 * chip.window_s < chip.busy_s <= chip.window_s
    layers = sum(chip.scope_seconds(p) for p in (
        "repro.hist_levels", "repro.split_gain", "repro.bin_features",
        "repro.traverse"))
    assert layers <= chip.busy_s
    # the top operations are named by scope, never as the enclosing loop
    labels = [k for k, _ in chip.top_ops]
    assert labels[0] == "repro.hist_levels[packed]/scatter-add"
    assert not any(k.startswith("while") for k in labels)
    assert {g[0] for g in chip.idle_gaps} <= {"bench.fit", "bench.request",
                                              "bench.prepare", "no span"}


def _chip_context(chip, kind):
    """What the harness hands a reader for the chip trace: one round of a
    65,536 x 18 fit, or three 1,024-row requests to 50 trees."""
    import harness
    import work
    if kind == "train":
        n, f, d, k = 65536, 18, 6, 32
        return harness.Context(
            device_kind="TPU v5 lite", units=1, unit_s=chip.window_s,
            work={"histogram": work.histogram(n, f, max_depth=d,
                                              n_candidates=k),
                  "binning": work.binning(n, f, n_candidates=k),
                  "split_gain": work.split_gain(f, max_depth=d,
                                                n_candidates=k),
                  "round": work.boosting_round(n, f, max_depth=d,
                                               n_candidates=k)},
            trace=chip)
    kw = dict(n_trees=50, max_depth=6)
    return harness.Context(
        device_kind="TPU v5 lite", units=3, unit_s=chip.window_s / 3,
        work={"traversal": work.traversal(1024, 115, **kw),
              "request": work.request(1024, 115, **kw)},
        trace=chip)


def _per_layer():
    import harness
    spec = harness.load_benchmark()
    kinds = {w["name"]: harness.load_mix(w["traffic"])["kind"]
             for w in spec["workloads"]}
    return [(m["name"], kinds[m["workloads"][0]]) for m in spec["per_layer"]]


@pytest.mark.parametrize("metric,kind", _per_layer())
def test_every_per_layer_reader_reads_a_chip_trace(chip, metric, kind):
    import harness
    value = harness.metric_reader(metric)(_chip_context(chip, kind))
    assert value is not None and value > 0
    if "roofline" in metric or "mfu" in metric:
        assert value <= 100.0
