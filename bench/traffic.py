"""The one general generator: turns a mix file and a seed into work.

A mix file (``bench/mixes/<name>.json``) holds only parameters.  Its
``kind`` names the driver (``bench/drive_<kind>.py``); the rest is read
here:

train
  ``rounds_per_call``: boosting rounds in each ``fit`` call of the
  window.  Every call gets a fresh key from the seed.

serve
  ``arrival``: ``{"type": "closed", "clients": 1}``: the next request
  leaves when the previous one's answer is ready; ``rows``: ``{"fixed":
  n}``, the rows of every request; ``check_requests``: how many served
  requests the reference compares after the window.  Rows are drawn
  from the configuration's data, a contiguous block from a uniform
  offset.

An arrival type or a size mix that a cell needs (an open loop, a mix
of sizes) is added here, with ``drive_serve``'s timing from when a request
was due.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator

import numpy as np

import datagen


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    start: int              # first row in the data pool
    rows: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [seed % (1 << 64), stream]))


def request_sizes(mix: dict) -> list[int]:
    """Every request size the mix sends (each is warmed up)."""
    return [int(mix["rows"]["fixed"])]


def requests(mix: dict, seed: int, pool_rows: int) -> Iterator[Request]:
    """The endless request stream of a serve mix, the same for a seed."""
    arrival = mix["arrival"]
    if arrival != {"type": "closed", "clients": 1}:
        raise ValueError(f"unsupported arrival {arrival!r}: one closed-loop "
                         f"client only")
    rng = _rng(seed, 2)
    (size,) = request_sizes(mix)
    if size > pool_rows:
        raise ValueError(f"request of {size} rows from a pool of "
                         f"{pool_rows}")
    for i in itertools.count():
        yield Request(i, int(rng.integers(0, pool_rows - size + 1)), size)


def call_key(seed: int, index: int, stream: int = 1):
    """The key of the ``index``-th ``fit`` call of a training window
    (``stream`` 1; the warm-up call draws from another stream)."""
    import jax
    return jax.random.fold_in(datagen.seed_key(seed, stream), index)
