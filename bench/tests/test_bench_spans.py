"""The program's host spans (``bench/spans.py``), and the per-layer
metrics on traces of the program before and after its layer scopes and
spans.

Two traces recorded on a TPU v5e by ``bench/record_trace.py`` (one
65,536 x 18 ``fit`` round, then three 1,024-row requests to a 50-tree
forest, under ``bench.window``):

- ``v5e_fit_predict.xplane.pb.gz``, from the program before it had the
  ``repro.route``/``repro.leaf_update``/``repro.proposal`` scopes and
  the ``repro.fit``/``repro.predict`` spans: the per-layer metrics the
  benchmark had read pinned values there, so a change to the reduction
  that moves them shows;
- ``v5e_fit_predict_spans.xplane.pb.gz``, from the program with them:
  every per-layer metric of ``BENCHMARK.json`` reads it, and the new
  scopes and spans are there.
"""

import collections
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import harness  # noqa: E402
import spans  # noqa: E402
import tracing  # noqa: E402
import work  # noqa: E402
from tracing import DeviceOps, Op, Span, Trace  # noqa: E402

DATA = BENCH / "tests" / "data"
OLD_TRACE = str(DATA / "v5e_fit_predict.xplane.pb.gz")
SPANS_TRACE = str(DATA / "v5e_fit_predict_spans.xplane.pb.gz")
MS = 1e6     # ns


def _trace():
    # window 0-100 ms; the device is busy 10-30, 60-70 and 95-100, so
    # the gaps are 0-10, 30-60 and 70-95
    ops = [Op(10 * MS, 30 * MS, "scatter.1",
              "jit(f)/repro.hist_levels[packed]/scatter"),
           Op(60 * MS, 70 * MS, "gather.2", "jit(f)/repro.route/gather"),
           Op(95 * MS, 100 * MS, "copy.3", "")]
    host = [Span(0, 100 * MS, "bench.window"),
            Span(28 * MS, 100 * MS, "bench.request"),
            Span(29 * MS, 52 * MS, "repro.predict"),
            Span(29 * MS, 40 * MS, "repro.predict.input"),
            Span(40 * MS, 41 * MS, "repro.predict.spec"),
            Span(41 * MS, 50 * MS, "repro.predict.dispatch"),
            Span(50 * MS, 52 * MS, "repro.predict.affine"),
            Span(75 * MS, 80 * MS, "repro.predict"),
            Span(110 * MS, 120 * MS, "repro.predict")]   # after the window
    return Trace({"/device:TPU:0": DeviceOps.of(ops)}, host)


def test_span_seconds_and_count_are_taken_inside_the_window():
    red = spans.reduce(_trace())
    assert red.span_count("repro.predict") == 2
    assert red.span_seconds("repro.predict") == pytest.approx(0.028)
    assert red.span_count("repro.predict.input") == 1
    assert red.span_seconds("bench.request") == pytest.approx(0.072)
    assert red.span_count("repro.fit") == 0
    assert red.span_seconds("repro.fit") == 0.0


def test_gaps_are_labelled_down_the_chain_of_spans():
    red = spans.reduce(_trace())
    gaps = {round(s * 1e3): name for name, s in red.idle_gaps}
    # 30-60: bench.request covers it all; inside it repro.predict
    # (22 ms) beats nothing else, and inside that the input (10 ms)
    # beats the dispatch (9 ms)
    assert gaps == {
        30: "bench.request>repro.predict>repro.predict.input",
        25: "bench.request>repro.predict",
        10: "no span"}


def test_a_tie_goes_to_the_longer_span():
    one = Span(0, 10 * MS, "a")
    two = Span(0, 20 * MS, "b")
    assert spans.chain(2 * MS, 8 * MS, [one, two]) == "b>a"
    assert spans.chain(12 * MS, 18 * MS, [one, two]) == "b"
    assert spans.chain(30 * MS, 40 * MS, [one, two]) == "no span"


def test_device_numbers_are_those_of_tracing():
    t = _trace()
    red, old = spans.reduce(t), tracing.reduce(t)
    assert (red.window_s, red.busy_s, red.n_devices, red.top_ops) == (
        old.window_s, old.busy_s, old.n_devices, old.top_ops)
    for prefix in ("repro.hist_levels", "repro.route", "repro."):
        assert red.scope_seconds(prefix) == old.scope_seconds(prefix)


def test_with_bench_spans_only_the_labels_are_those_of_tracing():
    red = spans.reduce(spans.load(OLD_TRACE))
    old = tracing.reduce(tracing.load(OLD_TRACE))
    assert red.idle_gaps == old.idle_gaps
    assert red.span_count("bench.request") == 3


OLD_PINS = {
    # the benchmark's readers on the older trace, as they read it when
    # the benchmark was made
    "hist_ms_per_round": 523.34575133,
    "hist_roofline": 0.0026898444818577168,
    "split_gain_ms_per_round": 0.4503026559998989,
    "binning_ms_per_round": 0.08240234399999678,
    "round_mfu_pct": 0.004605849616199321,
    "device_idle_pct.train": 2.6815488095356055,
    "traverse_ms_per_request": 6.605947239333152,
    "traverse_roofline": 0.009484479058644881,
    "serve_mfu_pct": 0.00033047851695218276,
    "device_idle_pct.serve": 2.6815488095356055,
}


def _per_layer():
    """Each per-layer metric with the kind of its first cell."""
    spec = harness.load_benchmark()
    cells = {w["name"]: harness.load_mix(w["traffic"])["kind"]
             for w in spec["workloads"]}
    return [(m["name"], cells[m["workloads"][0]]) for m in spec["per_layer"]]


def _context(red, kind):
    """What the harness hands a reader for a recorded trace: one round
    of a 65,536 x 18 fit, or three 1,024-row requests to 50 trees."""
    if kind == "train":
        n, f, d, k = 65536, 18, 6, 32
        return harness.Context(
            device_kind="TPU v5 lite", units=1, unit_s=red.window_s,
            work={"histogram": work.histogram(n, f, max_depth=d,
                                              n_candidates=k),
                  "round": work.boosting_round(n, f, max_depth=d,
                                               n_candidates=k)},
            trace=red)
    kw = dict(n_trees=50, max_depth=6)
    return harness.Context(
        device_kind="TPU v5 lite", units=3, unit_s=red.window_s / 3,
        work={"traversal": work.traversal(1024, 115, **kw),
              "request": work.request(1024, 115, **kw)},
        trace=red)


@pytest.fixture(scope="module")
def old_chip():
    return tracing.reduce(tracing.load(OLD_TRACE))


@pytest.mark.parametrize("metric,kind",
                         [mk for mk in _per_layer() if mk[0] in OLD_PINS])
def test_the_benchmarks_readers_read_the_older_trace_as_before(
        old_chip, metric, kind):
    value = harness.metric_reader(metric)(_context(old_chip, kind))
    assert value == pytest.approx(OLD_PINS[metric], rel=1e-12)


def test_the_older_trace_has_none_of_the_new_scopes_or_spans(old_chip):
    for prefix in ("repro.route", "repro.leaf_update", "repro.proposal"):
        assert old_chip.scope_seconds(prefix) == 0.0
    red = spans.reduce(spans.load(OLD_TRACE))
    assert red.span_count("repro.predict") == 0


@pytest.fixture(scope="module")
def chip():
    return spans.reduce(spans.load(SPANS_TRACE))


def test_the_spans_trace_has_the_programs_spans_nested_in_order(chip):
    names = collections.Counter(s.name for s in chip.spans)
    assert names == {"bench.fit": 1, "repro.fit": 1, "repro.fit.prepare": 1,
                     "bench.prepare": 3, "bench.request": 3,
                     "repro.predict": 3, "repro.predict.input": 3,
                     "repro.predict.spec": 3, "repro.predict.dispatch": 3,
                     "repro.predict.affine": 3}

    def within(name, parent):
        return [next(p for p in chip.spans if p.name == parent
                     and p.start <= s.start and s.end <= p.end)
                for s in chip.spans if s.name == name]

    assert len(within("repro.fit", "bench.fit")) == 1
    assert len(within("repro.fit.prepare", "repro.fit")) == 1
    assert len(set(map(id, within("repro.predict", "bench.request")))) == 3
    children = ["repro.predict.input", "repro.predict.spec",
                "repro.predict.dispatch", "repro.predict.affine"]
    for parent in [s for s in chip.spans if s.name == "repro.predict"]:
        inside = [s.name for s in chip.spans if s.name in children
                  and parent.start <= s.start and s.end <= parent.end]
        assert inside == children


@pytest.mark.parametrize("prefix,low,high", [
    ("repro.hist_levels", 0.40, 0.55),
    ("repro.route", 1e-4, 0.05),
    ("repro.leaf_update", 1e-5, 0.05),
    ("repro.proposal", 1e-7, 0.01),
    ("repro.traverse", 0.005, 0.03),
])
def test_the_spans_trace_gives_the_new_layers_time(chip, prefix, low, high):
    assert low < chip.scope_seconds(prefix) < high


def test_the_spans_trace_gives_the_predict_host_time(chip):
    # one repro.predict span per request, each inside its request and
    # a small part of it (the traversal runs after predict returns)
    assert chip.span_count("repro.predict") == 3
    host = chip.span_seconds("repro.predict") / 3
    request = chip.span_seconds("bench.request") / 3
    assert 1e-5 < host < 0.5 * request


@pytest.mark.parametrize("metric,kind", _per_layer())
def test_every_reader_reads_the_spans_trace(chip, metric, kind):
    value = harness.metric_reader(metric)(_context(chip, kind))
    assert value is not None
    assert value >= 0 if metric.startswith("unscoped_") else value > 0
    if "roofline" in metric or "mfu" in metric:
        assert value <= 100.0


def test_the_spans_trace_puts_little_device_time_under_no_scope(chip):
    unscoped = chip.busy_s - chip.scope_seconds("repro.")
    assert 0.0 <= unscoped < 0.02 * chip.busy_s
    assert bool(np.isfinite(unscoped))


def test_a_cpu_profile_holds_the_programs_spans_nested_in_order(tmp_path):
    import jax
    import jax.numpy as jnp

    import repro
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 4))
    y = (x[:, 0] > 0).astype(jnp.float32)
    cfg = repro.GBDTConfig(n_trees=2, max_depth=3, n_candidates=8)
    with jax.profiler.trace(str(tmp_path)):
        model = repro.fit(x, y, cfg)
        for i in range(3):
            model.predict(np.asarray(x[i * 64:(i + 1) * 64]),
                          output="margin").block_until_ready()
    got = [s for s in spans.load(tracing.find_xplane(str(tmp_path))).spans
           if s.name.startswith("repro.")]
    children = ["repro.predict.input", "repro.predict.spec",
                "repro.predict.dispatch", "repro.predict.affine"]
    assert [s.name for s in got] == (
        ["repro.fit", "repro.fit.prepare"] + 3 * (["repro.predict"]
                                                  + children))
    fit, prepare = got[:2]
    assert fit.start <= prepare.start and prepare.end <= fit.end
    for k in range(3):
        parent, *kids = got[2 + 5 * k: 7 + 5 * k]
        assert fit.end <= parent.start
        assert all(parent.start <= c.start and c.end <= parent.end
                   for c in kids)
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
