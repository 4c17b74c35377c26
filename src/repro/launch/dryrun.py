import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production mesh, record memory/cost analysis + collective schedule.

The two lines above MUST stay the first statements in this module — jax
locks the device count on first init, and the dry-run (and only the
dry-run) needs 512 placeholder host devices.

Usage:
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out-dir experiments/dryrun]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCH_NAMES, INPUT_SHAPES, get_config
from ..models.sharding import logical_rules, rules_for_mesh
from ..optim import AdamWConfig
from . import roofline, specs, steps
from .mesh import make_production_mesh
from .shardings import (batch_shardings, opt_shardings, param_shardings)


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def build_lowered(cfg, shape, mesh, opt_cfg=None, overrides=None):
    """Lower the right step function for (cfg, shape) on mesh."""
    opt_cfg = opt_cfg or AdamWConfig()
    if cfg.family == "moe" and cfg.moe_groups == 1:
        from .mesh import n_batch_devices
        cfg = dataclasses.replace(cfg, moe_groups=n_batch_devices(mesh))
    rules = rules_for_mesh(mesh, seq_shard=(cfg.seq_shard and
                                            shape.kind == "train"))
    window = specs.decode_window(cfg, shape)
    bspecs = specs.input_specs(cfg, shape)

    with jax.set_mesh(mesh), logical_rules(rules):
        if shape.kind == "train":
            pshapes, oshapes = steps.train_state_shapes(cfg, opt_cfg)
            pshard = param_shardings(pshapes, mesh, cfg)
            oshard = opt_shardings(oshapes, mesh, cfg)
            bshard = batch_shardings(bspecs, mesh)
            # pin accumulated grads to the ZeRO specs: per-microbatch grad
            # reductions become reduce-scatters instead of all-reduces
            fn = steps.make_train_step(
                cfg, opt_cfg, window=window,
                microbatches=cfg.train_microbatches,
                grad_shardings=(oshard["m"]
                                if cfg.train_microbatches > 1 else None))
            jitted = jax.jit(fn, in_shardings=(pshard, oshard, bshard),
                             out_shardings=(pshard, oshard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(pshapes, oshapes, bspecs)
            state_shapes = (pshapes, oshapes)
        elif shape.kind == "prefill":
            pshapes, _ = steps.train_state_shapes(cfg, opt_cfg)
            pshard = param_shardings(pshapes, mesh, cfg)
            bshard = batch_shardings(bspecs, mesh)
            fn = steps.make_prefill_step(cfg, window=window)
            jitted = jax.jit(fn, in_shardings=(pshard, bshard))
            lowered = jitted.lower(pshapes, bspecs)
            state_shapes = (pshapes,)
        else:  # decode
            pshapes, _ = steps.train_state_shapes(cfg, opt_cfg)
            pshard = param_shardings(pshapes, mesh, cfg)
            sshapes = specs.decode_state_specs(cfg, shape)
            sshard = specs.decode_state_shardings(cfg, shape, mesh)
            bshard = batch_shardings(bspecs, mesh)
            fn = steps.make_serve_step(cfg, window=window)
            jitted = jax.jit(fn, in_shardings=(pshard, sshard,
                                               bshard["tokens"],
                                               bshard["pos"]),
                             out_shardings=(None, sshard),
                             donate_argnums=(1,))
            lowered = jitted.lower(pshapes, sshapes, bspecs["tokens"],
                                   bspecs["pos"])
            state_shapes = (pshapes,)
    return lowered, state_shapes


# ---------------------------------------------------------------------------
# Delta cost measurement.
#
# XLA's cost_analysis counts while-loop bodies ONCE, so the scanned layer
# stack under-reports flops/bytes/collectives by ~n_layers.  We compile two
# small UNROLLED variants (1 and 2 layer-groups, scan_layers=False,
# scan_chunks=False) and extrapolate:   total = c1 + (n_units - 1) * (c2 - c1).
# Embedding/unembedding/frontend costs appear in both and cancel exactly in
# the delta; per-unit costs are identical across a uniform stack, so the
# extrapolation is exact up to XLA fusion noise.  The only loop that cannot
# be unrolled is sLSTM's time recurrence — corrected analytically below.
# ---------------------------------------------------------------------------

_DELTA_ATTN_CHUNK = 4096   # fewer unrolled kv blocks; flops unchanged


def _n_units(cfg) -> int:
    if cfg.family == "ssm":
        return cfg.n_layers // cfg.slstm_every
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def _delta_cfg(cfg, units: int):
    common = dict(scan_layers=False, scan_chunks=False,
                  attn_chunk=_DELTA_ATTN_CHUNK)
    if cfg.family == "ssm":
        return dataclasses.replace(cfg, n_layers=units * cfg.slstm_every,
                                   **common)
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, n_layers=units * cfg.attn_every,
                                   **common)
    if cfg.family == "audio":
        return dataclasses.replace(cfg, n_layers=units,
                                   n_encoder_layers=units, **common)
    return dataclasses.replace(cfg, n_layers=units, **common)


def _slstm_correction(cfg, shape) -> tuple[float, float]:
    """(flops, bytes) missing per sLSTM layer from its time-recurrence scan
    (body counted once; real trip count = seq_len)."""
    if cfg.family != "ssm" or shape.kind == "decode":
        return 0.0, 0.0
    b = shape.global_batch
    s = shape.seq_len
    h, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
    per_step_flops = 8.0 * b * h * dh * dh + 30.0 * b * h * dh
    per_step_bytes = 4.0 * (h * dh * 4 * dh) + 4.0 * 8 * b * h * dh
    mult = 3.0 if shape.kind == "train" else 1.0     # bwd + remat fwd
    n_sl = cfg.n_layers // cfg.slstm_every
    return (mult * n_sl * (s - 1) * per_step_flops,
            mult * n_sl * (s - 1) * per_step_bytes)


def _compile_cost(cfg, shape, mesh) -> dict:
    lowered, _ = build_lowered(cfg, shape, mesh)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    coll = roofline.collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def measure_cost(cfg, shape, mesh) -> dict:
    """Extrapolated whole-model cost via the delta method."""
    c1 = _compile_cost(_delta_cfg(cfg, 1), shape, mesh)
    c2 = _compile_cost(_delta_cfg(cfg, 2), shape, mesh)
    n = _n_units(cfg)
    ext = lambda a, b: max(a + (n - 1) * (b - a), 0.0)
    flops = ext(c1["flops"], c2["flops"])
    byts = ext(c1["bytes"], c2["bytes"])
    coll = {k: ext(c1["coll"][k], c2["coll"][k]) for k in c1["coll"]}
    sl_f, sl_b = _slstm_correction(cfg, shape)
    return {"flops": flops + sl_f, "bytes": byts + sl_b, "coll": coll,
            "delta_c1": c1, "delta_c2": c2, "n_units": n,
            "slstm_corr_flops": sl_f}


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            out_dir: str | None = None, cfg=None, mesh=None,
            verbose: bool = True) -> dict:
    cfg = cfg or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
           "kind": shape.kind, "status": "ok"}
    if not cfg.supports_shape(shape_name):
        rec["status"] = "skipped"
        rec["reason"] = "enc-dec full attention: no 500k decode (DESIGN.md)"
        return _finish(rec, out_dir, verbose)

    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    try:
        lowered, state_shapes = build_lowered(cfg, shape, mesh)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
        except Exception as e:  # backend without memory analysis
            rec["memory_analysis"] = {"error": str(e)}

        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        rec["flops_scanned_raw"] = float(cost.get("flops", -1))
        coll_raw = roofline.collective_bytes(compiled.as_text())
        rec["collective_bytes_scanned_raw"] = coll_raw

        # true whole-model cost via the delta method (single-pod only;
        # the multi-pod pass is the lowering proof, roofline is per-pod)
        if not multi_pod:
            meas = measure_cost(cfg, shape, mesh)
            rec["flops"] = meas["flops"]
            rec["bytes_accessed"] = meas["bytes"]
            rec["collective_bytes"] = meas["coll"]
            rec["delta_detail"] = {
                "c1": meas["delta_c1"], "c2": meas["delta_c2"],
                "n_units": meas["n_units"],
                "slstm_corr_flops": meas["slstm_corr_flops"]}
            rec["roofline"] = roofline.roofline_terms(
                {"flops": meas["flops"], "bytes accessed": meas["bytes"]},
                sum(meas["coll"].values()), n_chips)

        pshapes = state_shapes[0]
        n_params = roofline.count_params(pshapes)
        n_active = roofline.count_active_params(cfg, pshapes)
        tokens = shape.global_batch * (shape.seq_len
                                       if shape.kind != "decode" else 1)
        mf = roofline.model_flops(cfg, n_params, n_active, tokens,
                                  shape.kind)
        rec.update(n_params=n_params, n_active_params=n_active,
                   model_flops=mf, model_flops_per_chip=mf / n_chips)
        if rec.get("flops", 0) > 0:
            # compiled HLO flops are per-partition; compare like for like
            rec["useful_flops_ratio"] = (mf / n_chips) / rec["flops"]
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return _finish(rec, out_dir, verbose)


def _finish(rec: dict, out_dir: str | None, verbose: bool) -> dict:
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        r = rec.get("roofline", {})
        print(f"[dryrun] {rec['arch']:24s} {rec['shape']:12s} "
              f"{rec['mesh']:10s} {rec['status']:7s} "
              f"flops={rec.get('flops', 0):.3g} "
              f"dom={r.get('dominant', '-')}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.all or args.arch is None else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or args.shape is None \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_bad = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_one(a, s, multi_pod=mp, out_dir=args.out_dir)
                n_bad += rec["status"] == "error"
    if n_bad:
        raise SystemExit(f"{n_bad} dry-run combinations failed")


if __name__ == "__main__":
    main()
