"""``PredictReport`` throughput over the serving loop's wall time."""

import types

import numpy as np
import pytest

from repro.launch import serve_gbdt
from repro.launch.serve_gbdt import synthetic_gbdt
from repro.obs import PredictReport


def test_rows_per_s_is_taken_over_the_wall_time():
    lat = np.array([0.01, 0.01, 0.02])
    rep = PredictReport(latencies_s=lat, rows_per_request=100, engine={},
                        wall_s=0.08)
    s = rep.summarize()
    assert s["rows_per_s"] == pytest.approx(300 / 0.08)
    assert s["wall_s"] == pytest.approx(0.08)
    assert s["latency_ms"]["max"] == pytest.approx(20.0)


def test_without_a_wall_time_rows_per_s_falls_back_to_the_latencies():
    # the predict benchmark interleaves its variants in one loop, so a
    # variant's own time is the sum of its latencies
    rep = PredictReport(latencies_s=np.array([0.01, 0.03]),
                        rows_per_request=10, engine={})
    assert rep.summarize()["rows_per_s"] == pytest.approx(20 / 0.04)
    assert rep.summarize()["wall_s"] == pytest.approx(0.04)


def test_host_time_between_requests_lowers_the_served_rows_per_s(
        monkeypatch):
    # a clock that moves 1 ms per reading: each request reads 1 ms of
    # latency, and the loop's own readings between requests add 1 ms
    # more per request, so the wall time is about twice the latencies
    ticks = iter(np.arange(0.0, 10.0, 1e-3))
    clock = types.SimpleNamespace(perf_counter=lambda: next(ticks))
    monkeypatch.setattr(serve_gbdt, "time", clock)
    model = synthetic_gbdt(n_trees=4, max_depth=3, n_features=5)
    rep = serve_gbdt.serve(model, microbatch=16, n_requests=8)
    s = rep.summarize()
    by_latency = 16 * 8 / float(np.sum(rep.latencies_s))
    assert rep.wall_s > float(np.sum(rep.latencies_s))
    assert s["rows_per_s"] < 0.6 * by_latency
