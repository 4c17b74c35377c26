"""The benchmark harness: one run of one cell, found by name.

Everything a cell needs is found from ``BENCHMARK.json`` by name:

- ``bench/configs/<config>.json`` (the configuration's ``file``): sizes,
  tree settings and the data generator's parameters;
- ``bench/mixes/<traffic>.json``: the traffic mix; its ``kind`` picks
  the driver ``bench/drive_<kind>.py``, and :mod:`traffic` reads the
  rest;
- ``bench/limits/<workload>.json``: the limit of each number the
  reference compares;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric, a
  function ``read(ctx)`` of a :class:`Context` that returns a number or
  None where it finds nothing to read.

A run: set-up (data, model, warm-up of every shape the window uses),
the measured window, the peak device memory, then the reference's
check once the program's state is freed.  With ``--trace 1`` the
window runs under the profiler and the line carries the cell's
per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# Finding things by name.
# ---------------------------------------------------------------------------

def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def load_config(spec: dict, name: str) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    return json.loads((ROOT / entry["file"]).read_text())


def load_mix(name: str) -> dict:
    return json.loads((BENCH / "mixes" / f"{name}.json").read_text())


def load_limits(workload: str) -> dict:
    return json.loads((BENCH / "limits" / f"{workload}.json").read_text())


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` this cell reports: those that list it,
    and those without a list that move a metric it reports."""
    e2e_here = {m["name"] for m in spec["end_to_end"]
                if cell in m.get("workloads", [cell])}
    out = []
    for m in spec[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


# ---------------------------------------------------------------------------
# The device.
# ---------------------------------------------------------------------------

def require_accelerator(chips: int):
    """The cell's devices; exit non-zero where JAX finds no accelerator
    or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit("bench: JAX finds no accelerator (platform cpu); "
                         "nothing was measured")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}; nothing was measured")
    return devices[:chips]


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_cache() -> None:
    """JAX's persistent compilation cache, in the program's fixed
    directory inside the checkout (or ``JAX_COMPILATION_CACHE_DIR``),
    keeping every program of the run so that a second run compiles
    none."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts XLA compiles from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    device_kind: str
    units: int               # rounds or requests in the traced window
    unit_s: float            # window wall seconds per unit
    work: dict               # layer -> work.Work of one unit
    trace: object            # tracing.Reduction of the traced window


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

def parse_args(argv):
    p = argparse.ArgumentParser(description="Run one benchmark cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def _checks(numbers: dict, limits: dict) -> tuple[bool, dict]:
    out, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok &= good
        out[name] = {"value": value if value is None or math.isfinite(value)
                     else str(value), "limit": limit}
    return ok, out


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    spec = load_benchmark()
    cell = find_cell(spec, args.workload)
    devices = require_accelerator(int(cell["chips"]))

    import jax
    enable_cache()
    compiles = CompileCounter()

    config = load_config(spec, cell["config"])
    mix = load_mix(cell["traffic"])
    limits = load_limits(cell["name"])
    driver = importlib.import_module(f"drive_{mix['kind']}").Driver(
        config, mix, args.seed)
    driver.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = compiles.count
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            driver.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    in_window = compiles.count - c0
    peak = memory_peak(devices)
    units = driver.units

    metrics, breakdown = {}, None
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if args.trace:
        import tracing
        t_read = time.perf_counter()
        red = tracing.reduce(tracing.load(tracing.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace read and reduced in {time.perf_counter() - t_read:.1f}"
              f" s", file=sys.stderr, flush=True)
        ctx = Context(device_kind=dev.device_kind, units=units,
                      unit_s=driver.wall_s / max(units, 1),
                      work=driver.work(), trace=red)
        for m in cell_metrics(spec, cell["name"], "per_layer"):
            value = metric_reader(m["name"])(ctx)
            if value is None:
                print(f"per-layer metric {m['name']}: nothing to read in "
                      f"this trace, left out of the line", file=sys.stderr,
                      flush=True)
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
    else:
        measured = dict(driver.end_to_end(), setup_s=setup_s)
        for m in cell_metrics(spec, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}

    numbers = driver.check()
    ok, checks = _checks(numbers, limits)
    correct = ok and driver.failed == 0 and units > 0
    line = {"correct": correct, "attempted": driver.attempted,
            "failed": driver.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line.update(workload=cell["name"], seed=args.seed, units=units,
                window_wall_s=driver.wall_s, setup_s=setup_s,
                compiles_in_window=in_window, checks=checks)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
