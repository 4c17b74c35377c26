"""Work counts and the peak table, checked by hand at small shapes."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import pytest  # noqa: E402

import work  # noqa: E402


def test_histogram_counts_equal_a_hand_count():
    # n=10 rows, f=3 features, depth 2 (frontier 2), k=3 (4 bins):
    # per level 2*10*3 adds; bins 30 B + node ids 10 B + grad/hess 80 B
    # + a 2 x 3 x 4 x 2 float32 panel of 192 B; two levels
    w = work.histogram(10, 3, max_depth=2, n_candidates=3)
    assert w == work.Work(flops=120.0, bytes=2 * (30 + 10 + 80 + 192))


def test_binning_counts_equal_a_hand_count():
    # 10 x 3 values each compared with 3 candidates; read 120 B of x,
    # write 30 B of bin ids, read 36 B of candidates
    w = work.binning(10, 3, n_candidates=3)
    assert w == work.Work(flops=90.0, bytes=120 + 30 + 36)


def test_traversal_counts_equal_a_hand_count():
    # 4 rows x 5 features, 2 trees of depth 2 (3 inner nodes of 8 B,
    # 4 leaves of 4 B): rows 80 B, forest 2 * (24 + 16) = 80 B, margins
    # 16 B; 2 compares and 1 add per (row, tree)
    w = work.traversal(4, 5, n_trees=2, max_depth=2)
    assert w == work.Work(flops=24.0, bytes=80 + 80 + 16)
    assert work.request(4, 5, n_trees=2, max_depth=2) == work.Work(
        flops=24.0 + 8.0, bytes=176.0)


def test_round_holds_every_layer():
    kw = dict(max_depth=6, n_candidates=32)
    whole = work.boosting_round(5000, 18, **kw)
    parts = (work.histogram(5000, 18, **kw)
             + work.binning(5000, 18, n_candidates=32)
             + work.split_gain(18, **kw))
    assert whole.bytes > parts.bytes and whole.flops > parts.flops


@pytest.mark.parametrize("backend", ["auto", "packed", "pallas", "ref"])
def test_counts_do_not_depend_on_the_backend(backend):
    import drive_serve
    import drive_train
    base = {"rows": 4096, "features": 18, "data": {},
            "gbdt": {"max_depth": 6, "n_candidates": 32}}
    mix = {"kind": "train", "rounds_per_call": 1}
    want = drive_train.Driver(base, mix, 0).work()
    cfg = dict(base, gbdt=dict(base["gbdt"], backend=backend))
    assert drive_train.Driver(cfg, mix, 0).work() == want
    # traversal counts take the served requests' shapes only
    import traffic
    serve = drive_serve.Driver(
        {"rows": 1, "features": 115, "data": {},
         "forest": {"n_trees": 500, "max_depth": 6, "backend": backend}},
        {"kind": "serve"}, 0)
    serve.served = [(traffic.Request(0, 0, 1024), None)] * 3
    assert serve.work()["traversal"] == work.traversal(
        1024, 115, n_trees=500, max_depth=6)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        work.peaks("TPU v99")
    with pytest.raises(ValueError):
        work.Work(1.0, 1.0).least_seconds("cpu")


def test_least_seconds_is_the_larger_bound():
    pk = work.peaks("TPU v5 lite")
    w = work.Work(flops=pk["flops_per_s"], bytes=2 * pk["bytes_per_s"])
    assert w.least_seconds("TPU v5 lite") == pytest.approx(2.0)
    w = work.Work(flops=3 * pk["flops_per_s"], bytes=pk["bytes_per_s"])
    assert w.least_seconds("TPU v5 lite") == pytest.approx(3.0)
