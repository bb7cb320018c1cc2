"""Plain reference for distance answers: Dijkstra over the generated
edges, in integer arithmetic, with nothing taken from the program."""
from __future__ import annotations

import heapq

import numpy as np

from .network import Network

UNREACHED = -1


class Reference:
    """Single-source shortest distances on a ``Network``."""

    def __init__(self, net: Network):
        adj: list[list[tuple[int, int]]] = [[] for _ in
                                            range(net.num_vertices)]
        for a, b, w in zip(net.u.tolist(), net.v.tolist(), net.w.tolist()):
            adj[a].append((b, w))
            adj[b].append((a, w))
        self._adj = adj

    def distances_from(self, s: int) -> np.ndarray:
        """(n,) int64 distances from ``s``; ``UNREACHED`` where no path
        exists."""
        adj = self._adj
        dist = [UNREACHED] * len(adj)
        dist[s] = 0
        heap = [(0, s)]
        done = [False] * len(adj)
        while heap:
            d, x = heapq.heappop(heap)
            if done[x]:
                continue
            done[x] = True
            for y, w in adj[x]:
                nd = d + w
                if dist[y] == UNREACHED or nd < dist[y]:
                    dist[y] = nd
                    heapq.heappush(heap, (nd, y))
        return np.asarray(dist, dtype=np.int64)
