#!/usr/bin/env python3
"""Smoke run of the GBDT system on a TPU: train, check, serve, kernels.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py              # one chip, default phases
    python3 chip_smoke.py --chips 4    # the row-sharded trainer only

One chip, in one process:

  1. train: ``repro.fit`` on SUSY-like data at the published SUSY size
     (5,000,000 x 18), default config (random proposal re-proposed every
     round), depth 6, k = 32, 10 rounds; a short telemetry fit checks
     that the training loss falls;
  2. oracle: on a 262,144-row slice, ``fit`` and ``fit_reference`` on
     the chip give the same forest, and a fit on the host CPU reaches
     the chip's held-out accuracy within 0.5 pp;
  3. serve: the phase-1 model is saved and served through
     ``repro.launch.serve_gbdt.main``, then a synthetic forest of 500
     trees x depth 8 x 18 features, raw and binned; margins on one batch
     match a CPU evaluation with ``backend='ref'`` within 1e-5;
  4. pallas: each Pallas kernel on the chip matches its ``ref`` oracle
     on the CPU at small aligned shapes.

``--chips 4`` runs ``fit_distributed`` over a four-chip ``("data",)``
mesh on HIGGS-like data at the published HIGGS size (11,000,000 x 28),
with and without histogram subtraction, twice each (the repeat builds
no program), against its ``reference=True`` oracle and a one-chip
``fit`` on a slice, and nothing else.

Times printed here are smoke readings, not benchmarks.  A failed check
is printed and the run goes on, so one run reports every check; the
script then exits non-zero, as it does on any error, and before
compiling anything when JAX finds no TPU.  The last line of
standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                      # noqa: E402
import numpy as np                                              # noqa: E402
from jax.sharding import Mesh, NamedSharding                    # noqa: E402
from jax.sharding import PartitionSpec as P                     # noqa: E402

import repro                                                    # noqa: E402
from repro.core import distributed                              # noqa: E402
from repro.data import make_dataset                             # noqa: E402
from repro.kernels import ops, ref                              # noqa: E402
from repro.launch import serve_gbdt                             # noqa: E402
from repro.launch.compile_cache import enable_compile_cache     # noqa: E402

SEED = 0

# JAX's event for an XLA compile (compiles never nest, unlike traces);
# main() sums their durations
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration


# Failed checks, in order.  A failed check does not stop the run, so one
# chip run reports every check; main() then exits non-zero.
FAILURES: list[str] = []


def check(ok, what: str) -> None:
    if not ok:
        FAILURES.append(what)
    print(f"  {'ok' if ok else 'FAILED'}: {what}", flush=True)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def timed(fn, *args, **kwargs):
    """``(result, wall seconds, seconds of them in XLA compiles)``; the
    compile share counts once main() listens to JAX's compile events."""
    c0 = _compile_s[0]
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out if not hasattr(out, "forest") else out.forest)
    return out, time.perf_counter() - t0, _compile_s[0] - c0


def first_differing_tree(fa: repro.Forest, fb: repro.Forest) -> int | None:
    """Index of the first tree whose structure or values differ, with the
    repo's own tolerance for trainer equivalence (test_scan_trainer)."""
    for t in range(fa.n_trees):
        same = (np.array_equal(fa.feature[t], fb.feature[t])
                and np.array_equal(fa.split_bin[t], fb.split_bin[t])
                and np.allclose(fa.threshold[t], fb.threshold[t], atol=1e-6)
                and np.allclose(fa.leaf_value[t], fb.leaf_value[t],
                                atol=1e-5))
        if not same:
            return t
    return None


def host_forest(model: repro.GBDTModel) -> repro.Forest:
    return repro.Forest(*(np.asarray(a) for a in model.forest))


def host_accuracy(model: repro.GBDTModel, x, y) -> float:
    """Held-out accuracy of ``model``'s trees scored on the host CPU:
    on a TPU the traversal of 500,000 rows compiles for over a minute
    (PERF.md), a cost of the predict path, not of the trainer checked."""
    with jax.default_device(jax.devices("cpu")[0]):
        return repro.accuracy(dataclasses.replace(
            model, forest=host_forest(model)), x, y)


def split_agreement(fa: repro.Forest, fb: repro.Forest) -> float:
    """Share of inner nodes with the same (feature, split bin)."""
    same = (fa.feature == fb.feature) & (fa.split_bin == fb.split_bin)
    return float(np.mean(same))


# ---------------------------------------------------------------------------
# Phases: plain functions of their sizes, so tests run them on the CPU.
# ---------------------------------------------------------------------------

def phase_train(n_train: int, n_test: int, n_slice: int, *, rounds: int = 10,
                depth: int = 6, k: int = 32) -> dict:
    """Fit the default config at full scale; check it learns."""
    data = make_dataset("susy-like", n_train, n_test, seed=SEED)
    xtr, ytr, xte, yte, _ = data
    cfg = repro.GBDTConfig(n_trees=rounds, max_depth=depth, n_candidates=k)
    backend = cfg.hist_spec().resolved().backend
    log("train", f"susy-like {n_train} x {xtr.shape[1]}, {rounds} rounds, "
        f"depth {depth}, k={k}, strategy={cfg.strategy}, "
        f"resolved backend={backend}")
    key = jax.random.PRNGKey(SEED)
    x, y = jax.device_put(xtr), jax.device_put(ytr)
    # one fit only: the steady time is the fit less its XLA compiles
    model, fit_s, compile_s = timed(repro.fit, x, y, cfg, key)
    steady_s = (fit_s - compile_s) / rounds
    log("train", f"smoke reading: fit {fit_s:.3f} s, of which XLA compile "
        f"{compile_s:.3f} s; the rest is {steady_s:.4f} s/round")
    acc = repro.accuracy(model, xte, yte)
    log("train", f"held-out accuracy {acc:.4f} on {n_test} rows")
    check(acc > 0.8, f"held-out accuracy {acc:.4f} > 0.8")

    tcfg = dataclasses.replace(cfg, telemetry=True)
    rep = repro.fit(xtr[:n_slice], ytr[:n_slice], tcfg, key).report
    loss = np.asarray(rep.train_loss)
    log("train", "telemetry fit train loss per round: "
        + " ".join(f"{v:.5f}" for v in loss))
    check(np.all(np.isfinite(loss)) and loss[-1] < loss[0],
          f"training loss falls ({loss[0]:.5f} -> {loss[-1]:.5f})")
    return {"model": model, "data": data, "cfg": cfg, "backend": backend,
            "compile_s": compile_s, "steady_s_per_round": steady_s,
            "accuracy": acc}


def phase_oracle(data, cfg: repro.GBDTConfig, n_slice: int, cpu) -> dict:
    """fit == fit_reference on the chip; chip accuracy ~ CPU accuracy."""
    xtr, ytr, xte, yte, _ = data
    xs, ys = xtr[:n_slice], ytr[:n_slice]
    key = jax.random.PRNGKey(SEED)
    scan, scan_s, scan_c = timed(repro.fit, xs, ys, cfg, key)
    oracle, oracle_s, oracle_c = timed(repro.fit_reference, xs, ys, cfg, key)
    log("oracle", f"{n_slice} rows: fit {scan_s:.3f} s (XLA compile "
        f"{scan_c:.3f} s), fit_reference {oracle_s:.3f} s (XLA compile "
        f"{oracle_c:.3f} s)")
    fs, fo = host_forest(scan), host_forest(oracle)
    diff = first_differing_tree(fs, fo)
    bits = all(np.array_equal(a, b) for a, b in zip(fs, fo))
    log("oracle", f"fit vs fit_reference: first differing tree {diff}, "
        f"bit-identical={bits}")
    check(diff is None, "fit and fit_reference grow the same forest on the "
          "chip")

    with jax.default_device(cpu):
        host, host_s, _ = timed(repro.fit, xs, ys, cfg,
                                jax.random.PRNGKey(SEED))
        acc_cpu = repro.accuracy(host, xte, yte)
    acc_chip = repro.accuracy(scan, xte, yte)
    share = split_agreement(fs, host_forest(host))
    log("oracle", f"held-out accuracy chip {acc_chip:.4f} vs cpu "
        f"{acc_cpu:.4f} (cpu fit {host_s:.3f} s); identical splits "
        f"{share:.4f}")
    check(abs(acc_chip - acc_cpu) <= 0.005,
          f"chip accuracy within 0.5 pp of the CPU's "
          f"({100 * abs(acc_chip - acc_cpu):.3f} pp)")
    return {"accuracy_chip": acc_chip, "accuracy_cpu": acc_cpu,
            "identical_split_share": share, "scan_s": scan_s,
            "oracle_s": oracle_s}


def _margin_gap(model_on_chip, model_on_cpu, batch, cpu, binned) -> float:
    chip = np.asarray(model_on_chip.predict(batch, output="margin",
                                            binned=binned))
    with jax.default_device(cpu):
        want = np.asarray(model_on_cpu.predict(batch, output="margin",
                                               binned=binned, backend="ref"))
    check(chip.shape == want.shape and np.all(np.isfinite(chip)),
          f"margins finite, shape {chip.shape}")
    return float(np.max(np.abs(chip - want)))


def phase_serve(model: repro.GBDTModel, cpu, *, rows: int, requests: int,
                trees: int, depth: int, n_features: int) -> dict:
    """Serve the trained model and a synthetic forest via serve_gbdt."""
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        path = repro.save_gbdt(os.path.join(tmp, "model.npz"), model)
        synth = ["--trees", str(trees), "--depth", str(depth),
                 "--features", str(n_features)]
        runs = [("checkpoint", ["--ckpt", path], False),
                ("synthetic raw", synth, False),
                ("synthetic binned", synth + ["--binned"], True)]
        rng = np.random.default_rng(SEED + 1)
        for name, argv, binned in runs:
            report, total_s, compile_s = timed(serve_gbdt.main, argv + [
                "--microbatch", str(rows), "--requests", str(requests)])
            s = report.summarize()
            log("serve", f"{name}: smoke reading p50 "
                f"{s['latency_ms']['p50']:.3f} ms, p99 "
                f"{s['latency_ms']['p99']:.3f} ms, {s['rows_per_s']:.0f} "
                f"rows/s; whole run {total_s:.3f} s of which XLA compile "
                f"{compile_s:.3f} s")
            if name == "checkpoint":
                on_chip = repro.load_gbdt(path)
                with jax.default_device(cpu):
                    on_cpu = repro.load_gbdt(path)
            else:
                kw = dict(n_trees=trees, max_depth=depth,
                          n_features=n_features)
                on_chip = serve_gbdt.synthetic_gbdt(**kw)
                with jax.default_device(cpu):
                    on_cpu = serve_gbdt.synthetic_gbdt(**kw)
            f = on_chip.candidates.shape[1]
            batch = rng.normal(size=(rows, f)).astype(np.float32)
            gap = _margin_gap(on_chip, on_cpu, batch, cpu, binned)
            check(gap <= 1e-5, f"{name} margins within 1e-5 of the CPU ref "
                  f"(max abs diff {gap:.3g})")
            out[name] = {**s, "max_abs_margin_diff": gap,
                         "compile_s": compile_s}
    return out


def _split_gains64(hist: np.ndarray, l2: float = 1.0):
    """Per-bin split gains in float64 at ``ops.split_gain``'s defaults
    (gamma 0, min_child_weight 1e-6), and each (node, feature)'s total
    score ``G^2 / (H + l2)``."""
    g, h = hist[..., 0].astype(np.float64), hist[..., 1].astype(np.float64)
    gl, hl = np.cumsum(g, -1), np.cumsum(h, -1)
    gt, ht = gl[..., -1:], hl[..., -1:]

    def score(gg, hh):
        return gg * gg / (hh + l2)

    gain = 0.5 * (score(gl, hl) + score(gt - gl, ht - hl) - score(gt, ht))
    ok = (hl >= 1e-6) & (ht - hl >= 1e-6)
    ok[..., -1] = False
    return np.where(ok, gain, -np.inf), score(gt, ht)[..., 0]


def phase_pallas(backend: str, cpu, *, n: int = 4096, f: int = 28,
                 nbins: int = 33, n_nodes: int = 32, chunk: int = 25,
                 depth: int = 8, n_features: int = 18) -> dict:
    """Each Pallas kernel on the chip against its ref oracle on the CPU."""
    rng = np.random.default_rng(SEED + 2)
    bins = rng.integers(0, nbins, (n, f)).astype(np.int32)
    node = rng.integers(-1, n_nodes, (1, n)).astype(np.int32)
    gh = rng.normal(size=(n, 2)).astype(np.float32)
    out = {}

    def on_cpu(fn, *args, **kw):
        with jax.default_device(cpu):
            return fn(*(jax.device_put(a, cpu) for a in args), **kw)

    for subtract in (False, True):
        nn = n_nodes // 2 if subtract else n_nodes
        spec = ops.HistSpec(n_nodes=nn, nbins=nbins, backend=backend,
                            subtract=subtract)
        got = np.asarray(ops.hist_levels(bins, node, gh, spec))
        oracle = ref.hist_levels_left_ref if subtract else ref.hist_levels_ref
        want = np.asarray(on_cpu(oracle, bins, node, gh, n_nodes=nn,
                                 nbins=nbins))
        err = float(np.max(np.abs(got - want)))
        name = "hist_levels_left" if subtract else "hist_levels"
        check(got.shape == want.shape and np.allclose(got, want, rtol=1e-5,
                                                      atol=1e-4),
              f"{name}[{backend}] matches ref (max abs diff {err:.3g})")
        out[name] = err

    hist = np.abs(rng.normal(size=(n_nodes, f, nbins, 2))).astype(np.float32)
    gain, idx = (np.asarray(a) for a in ops.split_gain(hist, backend=backend))
    gain_r, idx_r = (np.asarray(a) for a in on_cpu(ref.split_gain_ref, hist))
    finite = np.isfinite(gain_r)
    # a gain is a difference of scores as large as the node's total
    # score, and the chip's f32 divide is not the CPU's correctly
    # rounded one: allow 16 ulps of that score, and let a bin tied with
    # the best within that tolerance stand for it
    gains64, total = _split_gains64(hist)
    atol = 16 * np.finfo(np.float32).eps * total
    diff = np.abs(gain - gain_r)
    chosen = np.take_along_axis(gains64, idx[..., None], -1)[..., 0]
    shortfall = gains64.max(-1) - chosen
    moved = int(np.sum(idx[finite] != idx_r[finite]))
    check(np.array_equal(finite, np.isfinite(gain))
          and np.all(diff[finite] <= atol[finite] + 1e-5 * np.abs(
              gain_r[finite]))
          and np.all(shortfall[finite] <= 2 * atol[finite]),
          f"split_gain[{backend}] matches ref (max abs gain diff "
          f"{np.max(diff[finite]):.3g} against a tolerance of at least "
          f"{np.min(atol):.3g}; {moved} of {int(finite.sum())} best bins "
          f"differ from ref, each within that tolerance of the best)")
    out["split_gain"] = float(np.max(diff[finite]))
    # the XLA ref on the same device as the kernel: if the two agree
    # there, the gap to the CPU ref is the device's arithmetic
    gain_d, idx_d = (np.asarray(a) for a in ref.split_gain_ref(hist))
    gap = float(np.max(np.abs(gain - gain_d)[finite]))
    log("pallas", f"split_gain[{backend}] vs ref on {jax.default_backend()}: "
        f"bit-identical={np.array_equal(gain, gain_d)}, max abs gain diff "
        f"{gap:.3g}, {int(np.sum(idx != idx_d))} best bins differ; ref on "
        f"{jax.default_backend()} vs ref on cpu: max abs gain diff "
        f"{float(np.max(np.abs(gain_d - gain_r)[finite])):.3g}")

    model = serve_gbdt.synthetic_gbdt(n_trees=chunk, max_depth=depth,
                                      n_features=n_features, seed=SEED + 3)
    x = rng.normal(size=(n // 4, n_features)).astype(np.float32)
    x[::7, 0] = np.nan
    fo = host_forest(model)
    for binned in (False, True):
        vals = np.asarray(model.bin_features(x), np.int32) if binned else x
        cmp = fo.split_bin if binned else fo.threshold
        spec = ops.TraverseSpec(tree_chunk=chunk, binned=binned,
                                backend=backend)
        got = np.asarray(ops.traverse_chunk(vals, fo.feature, cmp,
                                            fo.leaf_value, spec,
                                            max_depth=depth))
        want = np.asarray(on_cpu(ref.traverse_chunk_ref, vals, fo.feature,
                                 cmp, fo.leaf_value, max_depth=depth))
        name = f"traverse_chunk {'binned' if binned else 'raw'}"
        check(np.array_equal(got, want),
              f"{name}[{backend}] is bit-identical to ref")
        out[name] = 0.0
    return out


def phase_distributed(devices, *, n_train: int, n_test: int, n_slice: int,
                      rounds: int = 10, depth: int = 6, k: int = 32) -> dict:
    """Row-sharded fit over ``devices``, against its oracles.

    The training rows are laid out over the mesh once and used in place
    by every full-size call.  Each full-size fit runs twice with a new
    key: the repeat finds its program (no trace, no compile), so its
    time is the steady one.  The unrolled ``reference=True`` oracle and
    the one-device fit run on the first ``n_slice`` rows.  Accuracies
    are scored on the host (:func:`host_accuracy`); the per-device peaks are read
    before the one-device fit adds to the first device's."""
    t0 = time.perf_counter()
    xtr, ytr, xte, yte, _ = make_dataset("higgs-like", n_train, n_test,
                                         seed=SEED)
    mesh = Mesh(np.array(devices), ("data",))
    x = jax.device_put(xtr, NamedSharding(mesh, P("data", None)))
    y = jax.device_put(ytr, NamedSharding(mesh, P("data")))
    key = jax.random.PRNGKey(SEED)
    log("distributed", f"higgs-like {n_train} x {xtr.shape[1]} over "
        f"{len(devices)} devices, {rounds} rounds, depth {depth}, k={k}; "
        f"data made and laid out in {time.perf_counter() - t0:.1f} s")
    base = repro.GBDTConfig(n_trees=rounds, max_depth=depth, n_candidates=k)
    xs, ys = xtr[:n_slice], ytr[:n_slice]
    out = {}
    for subtract in (False, True):
        cfg = dataclasses.replace(base, subtract=subtract)
        tag = f"subtract={subtract}"
        _, first_s, compile_s = timed(repro.fit_distributed, x, y, cfg,
                                      mesh, key)
        built = distributed.sharded_program_count()
        model, fit_s, again_c = timed(repro.fit_distributed, x, y, cfg,
                                      mesh, jax.random.fold_in(key, 1))
        steady_s = fit_s / rounds
        acc = host_accuracy(model, xte, yte)
        log("distributed", f"{tag}: smoke reading first fit {first_s:.3f} s "
            f"(XLA compile {compile_s:.3f} s), repeat {fit_s:.3f} s "
            f"(compile {again_c:.3f} s): {steady_s:.4f} s/round; held-out "
            f"accuracy {acc:.4f}; {time.perf_counter() - t0:.1f} s in")
        check(acc > 0.8, f"{tag}: held-out accuracy {acc:.4f} > 0.8")
        check(distributed.sharded_program_count() == built,
              f"{tag}: the repeat fit builds no program")

        scan = repro.fit_distributed(xs, ys, cfg, mesh, key)
        oracle = repro.fit_distributed(xs, ys, cfg, mesh, key,
                                       reference=True)
        diff = first_differing_tree(host_forest(scan), host_forest(oracle))
        check(diff is None, f"{tag}: fit_distributed matches reference=True "
              f"tree for tree on {n_slice} rows (first diff {diff}; "
              f"{time.perf_counter() - t0:.1f} s in)")
        out[tag] = {"compile_s": compile_s, "steady_s_per_round": steady_s,
                    "accuracy": acc, "slice_model": scan}

    peaks = [peak_bytes(d) for d in devices]
    log("distributed", "peak_bytes_in_use per device: "
        + ", ".join("not reported" if p is None else str(p) for p in peaks)
        + f" (the whole x is {xtr.nbytes} bytes)")
    if None not in peaks:
        check(max(peaks) <= 1.25 * min(peaks),
              "per-device peak HBM about even (max <= 1.25 x min)")
    out["peak_bytes_in_use"] = peaks

    with jax.default_device(devices[0]):
        single = repro.fit(xs, ys, base, key)
    acc_single = host_accuracy(single, xte, yte)
    acc_dist = host_accuracy(out["subtract=False"].pop("slice_model"),
                             xte, yte)
    out["subtract=True"].pop("slice_model")
    log("distributed", f"{n_slice}-row slice: held-out accuracy sharded "
        f"{acc_dist:.4f} vs one device {acc_single:.4f}")
    check(abs(acc_dist - acc_single) <= 0.01,
          "sharded accuracy within 1 pp of the one-device fit")

    return out


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the row-sharded trainer on four chips")
    args = p.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
          f"compile cache: {cache}", flush=True)
    cpu = jax.devices("cpu")[0]
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    t0 = time.perf_counter()

    def run(name, fn, *a, **kw):
        out, wall_s, compile_s = timed(fn, *a, **kw)
        log(name, f"phase done: {wall_s:.3f} s wall, {compile_s:.3f} s in "
            f"XLA compiles, {wall_s - compile_s:.3f} s the rest (data, "
            f"tracing, runs, CPU references); peak_bytes_in_use "
            f"{peak_bytes(dev)}")
        return out

    if args.chips == 4:
        run("distributed", phase_distributed, devices[:4],
            n_train=11_000_000, n_test=500_000, n_slice=262_144)
    else:
        trained = run("train", phase_train, 5_000_000, 500_000, 262_144)
        run("oracle", phase_oracle, trained["data"], trained["cfg"], 262_144,
            cpu)
        run("serve", phase_serve, trained["model"], cpu, rows=4096,
            requests=16, trees=500, depth=8, n_features=18)
        run("pallas", phase_pallas, "pallas", cpu)

    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed after "
              f"{time.perf_counter() - t0:.1f} s: " + "; ".join(FAILURES),
              file=sys.stderr)
        return 1
    log("smoke", f"all checks passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
