"""The least work each layer needs, and the chip's peaks.

Operations and bytes are counted from a cell's shapes as the
*algorithm's* least work, the same whatever backend implements it: a
kernel reads its inputs and writes its outputs once, at the narrowest
type that holds them.  A faster kernel then cannot read above 100% of
its roofline unless it skips work.

- bin ids take 1 byte (nbins <= 256), level-local node ids 1 byte (a
  frontier of at most 256 nodes), grad/hess 8 bytes a row;
- the histogram reads bins, node ids and grad/hess once per level and
  writes its (nodes, features, bins, 2) float32 panel;
- a traversal reads the request's rows once, the forest once, and
  writes one float32 margin a row.
"""

from __future__ import annotations

import dataclasses

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16
        "bytes_per_s": 819e9,        # HBM
        "source": "Google Cloud documentation, 'TPU v5e' (system "
                  "architecture): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown chip is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/work.py")
    return PEAKS[device_kind]


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def least_seconds(self, device_kind: str) -> float:
        """The larger of operations over peak FLOP/s and bytes over peak
        bytes/s: the least time the chip could take."""
        pk = peaks(device_kind)
        return max(self.flops / pk["flops_per_s"],
                   self.bytes / pk["bytes_per_s"])


def _frontier(max_depth: int) -> int:
    return 2 ** max(max_depth - 1, 0)


def histogram(n: int, f: int, *, max_depth: int, n_candidates: int) -> Work:
    """One tree's grad/hess histograms: every level adds each row's
    grad and hess into one bin per feature."""
    nbins = n_candidates + 1
    panel = _frontier(max_depth) * f * nbins * 2 * 4
    per_level = Work(flops=2.0 * n * f, bytes=n * f + n + 8.0 * n + panel)
    return per_level * max_depth


def binning(n: int, f: int, *, n_candidates: int) -> Work:
    """Compare each value with the candidates; read x, write bin ids."""
    return Work(flops=float(n) * f * n_candidates,
                bytes=4.0 * n * f + n * f + 4.0 * f * n_candidates)


def split_gain(f: int, *, max_depth: int, n_candidates: int) -> Work:
    """Prefix sums and gains over each level's histogram panel."""
    nbins = n_candidates + 1
    cells = _frontier(max_depth) * f * nbins
    return Work(flops=12.0 * cells, bytes=8.0 * cells + 8.0 * cells / nbins) \
        * max_depth


def boosting_round(n: int, f: int, *, max_depth: int,
                   n_candidates: int) -> Work:
    """A whole round: grad/hess, proposal, binning, histogram, split
    gain, routing each row one level down per level, leaf values and the
    margin update."""
    k = n_candidates
    grad_hess = Work(flops=6.0 * n, bytes=8.0 * n + 8.0 * n)
    proposal = Work(flops=0.0, bytes=2 * 4.0 * f * k)
    route = Work(flops=2.0 * n, bytes=n + n + n) * max_depth
    leaves = Work(flops=3.0 * n, bytes=n + 8.0 * n + 4.0 * n)
    return (grad_hess + proposal + binning(n, f, n_candidates=k)
            + histogram(n, f, max_depth=max_depth, n_candidates=k)
            + split_gain(f, max_depth=max_depth, n_candidates=k)
            + route + leaves)


def traversal(rows: int, f: int, *, n_trees: int, max_depth: int) -> Work:
    """Descend every row through every tree: one compare a level and
    one add a tree; read rows and forest once, write the margins."""
    n_inner, n_leaves = 2 ** max_depth - 1, 2 ** max_depth
    forest = n_trees * (n_inner * (4 + 4) + n_leaves * 4)
    return Work(flops=float(rows) * n_trees * (max_depth + 1),
                bytes=4.0 * rows * f + forest + 4.0 * rows)


def request(rows: int, f: int, *, n_trees: int, max_depth: int) -> Work:
    """A whole scoring request: the traversal and ``base + lr * sum``,
    which needs no bytes beyond the margins the traversal writes."""
    return traversal(rows, f, n_trees=n_trees, max_depth=max_depth) \
        + Work(flops=2.0 * rows, bytes=0.0)
