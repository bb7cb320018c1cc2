"""The Border-Labeling index build as one JAX program (the TPU path).

Shows the composable core module: dense packed districts → vmapped
Bellman-Ford (stage A) → overlay closure (stage B) → full-table min-plus
(stage C) → rank-ordered prune (stage D), with the Pallas kernels
switched in, validated against the Dijkstra-based reference builder. On
the CPU the kernels run in interpret mode. For a TPU they lower through
Mosaic: ``tests/test_tpu_compile.py`` compiles ``minplus_pallas``,
``relax_pallas`` and ``floyd_warshall_pallas`` for a described v5e.

    PYTHONPATH=src python examples/tpu_index_build.py
"""
import time

import jax
import numpy as np

from repro.core import (bfs_grow_partition, build_border_labels_reference,
                        grid_road_network)
from repro.core.jax_builder import build_border_labels_jax


def main() -> None:
    g = grid_road_network(24, 24, seed=5)
    part = bfs_grow_partition(g, 6, seed=0)

    t0 = time.perf_counter()
    ref = build_border_labels_reference(g, part)
    t_ref = time.perf_counter() - t0

    t0 = time.perf_counter()
    jax_bl = build_border_labels_jax(g, part, use_pallas=True)
    t_jax = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    ss = rng.integers(0, g.num_vertices, size=200)
    ts = rng.integers(0, g.num_vertices, size=200)
    np.testing.assert_allclose(jax_bl.query_many(ss, ts),
                               ref.query_many(ss, ts), rtol=1e-5)
    print(f"reference (pruned Dijkstra) : {t_ref*1e3:7.1f} ms")
    mode = ("Pallas interpret" if jax.default_backend() == "cpu"
            else "Pallas")
    print(f"JAX pipeline ({mode:16s}): {t_jax*1e3:7.1f} ms")
    print(f"borders={jax_bl.num_borders}, "
          f"index={jax_bl.size_bytes()/1e6:.2f} MB — answers match on "
          f"200 random queries")


if __name__ == "__main__":
    main()
