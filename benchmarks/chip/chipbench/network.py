"""The benchmark's own road-network generator.

A copy of the synthetic-continent arithmetic: a ``gx x gy`` mosaic of
``r x c`` grid districts. District interiors are full grid meshes;
adjacent districts are joined by ``border_links`` crossing edges per
shared boundary segment. Weights are integers drawn uniformly from
``1..weight_high``, so every shortest distance is an integer.

The random draws are made in the same order as the program's
generator, so the same seed and shape give the same edges (a test ties
the two). The copy lives here so that the yardstick cannot move when
the program's generator changes.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Network:
    """An undirected road network with its district partition.

    ``u``, ``v``, ``w`` list every edge once (integer weights);
    ``district[x]`` is the district of vertex ``x``; districts are
    numbered row-major over the ``grid``."""
    num_vertices: int
    u: np.ndarray           # (m,) int64
    v: np.ndarray           # (m,) int64
    w: np.ndarray           # (m,) int64
    district: np.ndarray    # (n,) int32
    grid: tuple[int, int]
    district_shape: tuple[int, int]

    @property
    def num_districts(self) -> int:
        return self.grid[0] * self.grid[1]

    def district_xy(self) -> np.ndarray:
        """(num_districts, 2) grid coordinates (column, row) of each
        district."""
        gx = self.grid[0]
        ids = np.arange(self.num_districts)
        return np.stack([ids % gx, ids // gx], axis=1)

    def members(self) -> list[np.ndarray]:
        """Vertices of each district, ascending."""
        order = np.argsort(self.district, kind="stable")
        counts = np.bincount(self.district, minlength=self.num_districts)
        return np.split(order, np.cumsum(counts)[:-1])


def continent(grid: tuple[int, int], district: tuple[int, int],
              border_links: int, weight_high: int,
              rng: np.random.Generator) -> Network:
    """Generate the continent from ``rng`` (see module docstring)."""
    gx, gy = int(grid[0]), int(grid[1])
    r, c = int(district[0]), int(district[1])
    if gx < 1 or gy < 1 or r < 2 or c < 2 or border_links < 1 \
            or weight_high < 1:
        raise ValueError(f"bad continent shape grid={grid} "
                         f"district={district} border_links="
                         f"{border_links} weight_high={weight_high}")
    H, W = gy * r, gx * c
    us: list[np.ndarray] = []
    vs: list[np.ndarray] = []
    ws: list[np.ndarray] = []

    def emit(a: np.ndarray, b: np.ndarray) -> None:
        us.append(a)
        vs.append(b)
        ws.append(rng.integers(1, weight_high + 1, size=len(a)))

    # streets inside districts: every grid edge that crosses no boundary
    rows = np.arange(H, dtype=np.int64)
    cols = np.arange(W - 1, dtype=np.int64)
    cols = cols[(cols + 1) % c != 0]
    a = (rows[:, None] * W + cols[None, :]).ravel()
    emit(a, a + 1)
    rows = np.arange(H - 1, dtype=np.int64)
    rows = rows[(rows + 1) % r != 0]
    cols = np.arange(W, dtype=np.int64)
    a = (rows[:, None] * W + cols[None, :]).ravel()
    emit(a, a + W)

    # links between districts: border_links crossings per boundary
    k = min(border_links, r, c)
    bu: list[np.ndarray] = []
    bv: list[np.ndarray] = []
    for bx in range(1, gx):
        col = bx * c - 1
        for jy in range(gy):
            pick = rng.choice(r, size=k, replace=False) + jy * r
            x = pick.astype(np.int64) * W + col
            bu.append(x)
            bv.append(x + 1)
    for by in range(1, gy):
        row = by * r - 1
        for jx in range(gx):
            pick = rng.choice(c, size=k, replace=False) + jx * c
            x = row * W + pick.astype(np.int64)
            bu.append(x)
            bv.append(x + W)
    if bu:
        emit(np.concatenate(bu), np.concatenate(bv))

    drow = np.arange(H, dtype=np.int64) // r
    dcol = np.arange(W, dtype=np.int64) // c
    assign = (drow[:, None] * gx + dcol[None, :]).ravel().astype(np.int32)
    return Network(H * W, np.concatenate(us), np.concatenate(vs),
                   np.concatenate(ws).astype(np.int64), assign,
                   (gx, gy), (r, c))
