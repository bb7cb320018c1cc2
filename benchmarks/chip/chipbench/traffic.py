"""One general generator for every traffic mix.

A mix is a data file (``traffic/<name>.json``). Its requests come
from a pool of ``pool`` requests, sent back to back from one client
(``driver``: ``closed_loop``), and its ``pairs`` block says how the
(s, t) pairs are drawn:

* ``trips`` -- the origin district is Zipf(``origin_zipf``) over a
  seeded ranking of districts; a share ``same_district`` of trips ends
  in the origin district, the rest in another district drawn with
  weight ``hop_decay ** grid_hops``; vertices are uniform inside their
  district; ``per_request`` trips make one request;
* ``matrix`` -- one-to-many matrices of ``sources`` x ``targets``
  vertices drawn uniformly, flattened source-major into one request.

A request is ``rows`` rows of ``width`` pairs: a matrix's row is one
source against every target; a batch of trips is one row. The number
of requests and their sizes depend only on the mix; the seed changes
which pairs are asked and in what order, never how much work there is.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .network import Network


def trip_pairs(net: Network, num: int, rng: np.random.Generator, *,
               origin_zipf: float, same_district: float,
               hop_decay: float) -> tuple[np.ndarray, np.ndarray]:
    """``num`` (s, t) trips (see module docstring)."""
    m = net.num_districts
    ranking = rng.permutation(m)
    zipf = 1.0 / np.arange(1, m + 1) ** origin_zipf
    p_origin = np.empty(m)
    p_origin[ranking] = zipf / zipf.sum()
    origin = rng.choice(m, size=num, p=p_origin)
    dest = origin.copy()
    away = rng.random(num) >= same_district
    if m > 1:
        xy = net.district_xy()
        hops = np.abs(xy[:, None, :] - xy[None, :, :]).sum(axis=2)
        weight = hop_decay ** hops.astype(np.float64)
        np.fill_diagonal(weight, 0.0)
        weight /= weight.sum(axis=1, keepdims=True)
        for d in range(m):
            sel = np.nonzero(away & (origin == d))[0]
            if len(sel):
                dest[sel] = rng.choice(m, size=len(sel), p=weight[d])
    members = net.members()
    sizes = np.array([len(x) for x in members])
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    flat = np.concatenate(members)

    def vertex_in(d: np.ndarray) -> np.ndarray:
        pick = (rng.random(len(d)) * sizes[d]).astype(np.int64)
        return flat[starts[d] + pick]

    return vertex_in(origin), vertex_in(dest)


@dataclass
class MatrixPool:
    """A pool of one-to-many requests, sent back to back in order."""
    sources: np.ndarray     # (P, S) int32
    targets: np.ndarray     # (P, T) int32

    @property
    def rows(self) -> int:
        return self.sources.shape[1]

    @property
    def width(self) -> int:
        return self.targets.shape[1]

    def request(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        """Request ``i`` (cycling over the pool) as flattened pairs."""
        src = self.sources[i % len(self.sources)]
        dst = self.targets[i % len(self.targets)]
        return (np.repeat(src.astype(np.int64), len(dst)),
                np.tile(dst.astype(np.int64), len(src)))

    def row_pairs(self, i: int, r: int) -> tuple[np.ndarray, np.ndarray]:
        """The pairs of row ``r`` of request ``i``."""
        dst = self.targets[i % len(self.targets)].astype(np.int64)
        return np.full(len(dst), self.sources[i % len(self.sources)][r],
                       dtype=np.int64), dst


@dataclass
class TripPool:
    """A pool of requests of ``per_request`` trips each, sent back to
    back in order; a request is one row."""
    ss: np.ndarray          # (P, B) int32
    ts: np.ndarray          # (P, B) int32
    rows = 1

    @property
    def width(self) -> int:
        return self.ss.shape[1]

    def request(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        """Request ``i`` (cycling over the pool)."""
        return self.row_pairs(i, 0)

    def row_pairs(self, i: int, r: int) -> tuple[np.ndarray, np.ndarray]:
        j = i % len(self.ss)
        return self.ss[j].astype(np.int64), self.ts[j].astype(np.int64)


def generate(mix: dict, net: Network, rng: np.random.Generator):
    """The request pool of ``mix``."""
    if mix["driver"] != "closed_loop":
        raise ValueError(f"unknown driver {mix['driver']!r}")
    pairs = mix["pairs"]
    pool = int(mix["pool"])
    if pairs["kind"] == "trips":
        size = int(pairs["per_request"])
        ss, ts = trip_pairs(net, pool * size, rng,
                            origin_zipf=pairs["origin_zipf"],
                            same_district=pairs["same_district"],
                            hop_decay=pairs["hop_decay"])
        return TripPool(ss.astype(np.int32).reshape(pool, size),
                        ts.astype(np.int32).reshape(pool, size))
    if pairs["kind"] == "matrix":
        n = net.num_vertices
        return MatrixPool(
            rng.integers(0, n, size=(pool, pairs["sources"]),
                         dtype=np.int32),
            rng.integers(0, n, size=(pool, pairs["targets"]),
                         dtype=np.int32))
    raise ValueError(f"unknown pairs kind {pairs['kind']!r}")
