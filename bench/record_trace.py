"""Record the small chip trace that ``bench/tests`` read.

One ``bench.window`` holding a ``repro.fit`` of one round on 65,536 x
18 rows (depth 6, k 32) under ``bench.fit``, then three 1,024-row
requests (``bench.prepare``, ``bench.request``) to a 50-tree depth-6
forest on 115 features, every shape warmed up first.  The profiler's
plane of HLO protos and the operations' source locations are left out,
and the file is refused if it still names a directory of the machine
it was recorded on.  On a chip::

    python3 bench/record_trace.py OUT.xplane.pb.gz
"""

from __future__ import annotations

import argparse
import gzip
import os
import sys
import tempfile

import jax
import numpy as np

import datagen
import tracing

DROPPED_PLANES = ("/host:metadata",)
DROPPED_STATS = ("source", "source_stack")


def record(out: str) -> None:
    """Record the trace and write it, stripped, to ``out``."""
    import repro
    from repro.launch.serve_gbdt import synthetic_gbdt
    x, y = datagen.mixture(datagen.seed_key(0, 0), n=65536, f=18,
                           sep=1.2, flip=0.05, positive_share=0.458)
    cfg = repro.GBDTConfig(n_trees=1, max_depth=6)
    model = synthetic_gbdt(n_trees=50, max_depth=6, n_features=115)
    rows = 1024
    pool = np.random.default_rng(0).normal(
        size=(3 * rows, 115)).astype(np.float32)
    key = jax.random.PRNGKey(1)
    jax.block_until_ready(repro.fit(x, y, cfg, key).forest)
    for _ in range(2):
        model.predict(pool[:rows], output="margin").block_until_ready()

    directory = tempfile.mkdtemp(prefix="bench-record-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.fit"):
                jax.block_until_ready(repro.fit(x, y, cfg, key).forest)
            for i in range(3):
                with jax.profiler.TraceAnnotation("bench.prepare"):
                    xb = pool[i * rows:(i + 1) * rows]
                with jax.profiler.TraceAnnotation("bench.request"):
                    model.predict(xb, output="margin").block_until_ready()
    finally:
        jax.profiler.stop_trace()
    write(tracing.find_xplane(directory), out)


def write(raw: str, out: str) -> None:
    """Write the trace ``raw`` to ``out`` without the plane of HLO protos
    and the operations' source locations (``source``, ``source_stack``),
    which name the checkout's directory."""
    space = tracing.read_xspace(raw)
    for i in reversed(range(len(space.planes))):
        if space.planes[i].name in DROPPED_PLANES:
            del space.planes[i]
    for plane in space.planes:
        dropped = {e.key for e in plane.stat_metadata
                   if e.value.name in DROPPED_STATS}
        for entry in plane.event_metadata:
            stats = entry.value.stats
            for i in reversed(range(len(stats))):
                if stats[i].metadata_id in dropped:
                    del stats[i]
    data = space.SerializeToString()
    named = [d for d in {os.getcwd(), os.path.dirname(raw),
                         tempfile.gettempdir(), os.path.expanduser("~"),
                         sys.prefix}
             if len(d) > 1 and d.encode() in data]
    if named:
        raise SystemExit(f"the trace names {named}; not written")
    with gzip.open(out, "wb") as fh:
        fh.write(data)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("out")
    args = p.parse_args(argv)
    if jax.devices()[0].platform == "cpu":
        raise SystemExit("record_trace: no accelerator; nothing recorded")
    sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    record(args.out)


if __name__ == "__main__":
    main()
