"""The general generator: mixes turn into the same work for a seed."""

import itertools
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import traffic  # noqa: E402

CLOSED = {"kind": "serve", "arrival": {"type": "closed", "clients": 1},
          "rows": {"fixed": 1024}, "check_requests": 4}

def _take(mix, seed, n=2000, pool=10_000):
    return list(itertools.islice(traffic.requests(mix, seed, pool), n))


def test_closed_loop_sends_fixed_rows_from_the_pool():
    reqs = _take(CLOSED, 2 ** 33 + 1)
    assert all(r.rows == 1024 for r in reqs)
    assert all(0 <= r.start <= 10_000 - 1024 for r in reqs)
    assert [r.index for r in reqs] == list(range(len(reqs)))
    assert traffic.request_sizes(CLOSED) == [1024]


def test_offsets_cover_the_pool_uniformly():
    starts = np.array([r.start for r in _take(CLOSED, 5, n=4000)])
    assert starts.min() < 200 and starts.max() > 10_000 - 1024 - 200
    assert np.mean(starts) == pytest.approx((10_000 - 1024) / 2, rel=0.05)


def test_a_seed_gives_the_same_stream_and_another_seed_another():
    assert _take(CLOSED, 9, 50) == _take(CLOSED, 9, 50)
    assert _take(CLOSED, 9, 50) != _take(CLOSED, 10, 50)


@pytest.mark.parametrize("change", [
    {"arrival": {"type": "closed", "clients": 4}},
    {"arrival": {"type": "poisson", "rate_per_s": 50.0}},
    {"arrival": {"type": "bursty"}},
])
def test_mixes_the_driver_cannot_run_are_refused(change):
    with pytest.raises(ValueError):
        _take(dict(CLOSED, **change), 1)


def test_a_request_larger_than_the_pool_is_refused():
    with pytest.raises(ValueError):
        _take(CLOSED, 1, pool=100)


def test_call_keys_differ_per_call_and_stream():
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        keys = {tuple(np.asarray(traffic.call_key(7, i, s)))
                for i in range(3) for s in (1, 3)}
    assert len(keys) == 6


def test_a_closed_loop_window_times_each_request_and_checks_margins():
    import drive_serve
    config = {"rows": 4096, "features": 8, "data": {},
              "forest": {"n_trees": 10, "max_depth": 4, "n_candidates": 8,
                         "learning_rate": 0.3, "passthrough_frac": 0.1,
                         "leaf_scale": 0.1}}
    mix = dict(CLOSED, rows={"fixed": 64})
    driver = drive_serve.Driver(config, mix, 3)
    driver.setup()
    driver.window(0.5)
    assert driver.failed == 0 and driver.units == driver.attempted >= 10
    assert driver.wall_s >= 0.5
    assert len(driver.latencies) == driver.attempted
    assert sum(driver.latencies) <= driver.wall_s
    assert driver.check()["margin_gap"] < 1e-5
