"""BENCHMARK.json holds to the benchmark's contract, and every name in it
finds its file."""

import json
import pathlib
import re
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    assert 1 <= len(SPEC["command"]) <= 32
    for word in SPEC["command"]:
        assert _text_ok(word) and not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51


def test_a_full_check_of_24_cells_fits_its_time():
    rs = SPEC["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", list(KEYS))
def test_entries_have_just_their_keys(section):
    entries = SPEC[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")


def test_configs_and_cells():
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    assert 1 <= len(cfgs) <= 24 and 1 <= len(SPEC["workloads"]) <= 24
    files = [c["file"] for c in cfgs.values()]
    assert len(files) == len(set(files))
    for c in cfgs.values():
        assert _text_ok(c["source"]) and _text_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert len(c["reduced"]) <= 16
    used = set()
    pairs = set()
    for w in SPEC["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert _text_ok(w["why"]) and NAME.match(w["traffic"])
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        mix = harness.load_mix(w["traffic"])
        assert (BENCH / f"drive_{mix['kind']}.py").exists()
        assert harness.load_limits(w["name"])
    assert used == set(cfgs)
    assert len(pairs) == len(SPEC["workloads"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_metrics():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and _text_ok(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
        layers.setdefault(m["layer"], set()).add(m["name"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in cells:
        reported = {m["name"] for m in harness.cell_metrics(SPEC, w,
                                                            "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(SPEC, w, "per_layer")


def test_every_metric_reader_is_listed():
    listed = {m["name"] for m in SPEC["per_layer"]}
    found = {p.name[:-3] for p in (BENCH / "metrics").glob("*.py")}
    assert found == listed
