"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jax lowers each kernel and
jitted step for a ``v5e:2x2`` topology that is described, not attached,
and each compiled program must hold the Mosaic kernel
(``tpu_custom_call``). Interpret mode accepts code that Mosaic refuses
(dynamic slices in a loop body, unaligned lane indexing); these compiles
catch that without a chip. Shapes are the serving and builder widths of
``chip_smoke.py``'s one-chip deployment.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers
import every test file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.edge import engine as eng
from repro.edge.sharded_oracle import make_sharded_query_fn
from repro.kernels.label_join import ops as lj
from repro.kernels.label_join.kernel import join_lb_pallas, join_pallas
from repro.kernels.minplus.kernel import minplus_pallas, relax_pallas
from repro.kernels.sssp_relax.kernel import floyd_warshall_pallas

# chip_smoke.py's one-chip deployment: 17x17 districts of 16x16 vertices
N, Q_BORDERS, KMAX, BMAX = 73984, 2166, 256, 8
WIDTH = max(KMAX, Q_BORDERS)
BATCH = 4096
SHARDS = 4
# the dense-matrix benchmark cell: 6x6 districts, q = 928, 256x256 pairs
DENSE_ROWS, DENSE_WIDTH, DENSE_BATCH = 18432, 928, 65536
# the mesh4-trips benchmark cell: 8x8 districts of 256 vertices, 16 a
# chip, q about 1,101 (= W), 1024-trip requests
MESH_N, MESH_Q, MESH_DPD, MESH_BATCH = 16384, 1101, 16, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # libtpu would otherwise write its logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def kernels_on(monkeypatch):
    """Steer the serving joins to the Mosaic kernel: on this CPU host
    ``_on_cpu`` would pick interpret mode."""
    monkeypatch.setattr(lj, "_on_cpu", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _single_chip_lowered(case, one):
    f32 = lambda *s: _sds(s, jnp.float32, one)           # noqa: E731
    rows = _sds((BATCH,), jnp.int32, one)
    table_rows = N // KMAX * KMAX + N
    if case == "join":
        return join_pallas.lower(f32(BATCH, WIDTH), f32(BATCH, WIDTH))
    if case == "join_lb":
        return join_lb_pallas.lower(f32(BATCH, WIDTH), f32(BATCH, WIDTH))
    if case == "engine_step":
        return eng._engine_fn.lower(f32(table_rows, WIDTH), rows, rows,
                                    use_pallas=True)
    if case == "engine_step_quantized":
        table = _sds((table_rows, WIDTH), jnp.uint16, one)
        return eng._engine_fn_quantized.lower(
            table, rows, rows, use_pallas=True, sentinel=65535, scale=1.0)
    if case == "minplus":            # stage B overlay closure square
        return minplus_pallas.lower(f32(Q_BORDERS, Q_BORDERS),
                                    f32(Q_BORDERS, Q_BORDERS))
    if case == "relax":              # stage A border sweep of one district
        return relax_pallas.lower(f32(BMAX, KMAX), f32(KMAX, KMAX))
    if case == "floyd_warshall":     # one district's APSP
        return floyd_warshall_pallas.lower(f32(KMAX, KMAX))
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "join", "join_lb", "engine_step", "engine_step_quantized",
    "minplus", "relax", "floyd_warshall"])
def test_single_chip_kernel_compiles(topo, kernels_on, case):
    one = SingleDeviceSharding(topo.devices[0])
    compiled = _single_chip_lowered(case, one).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_quantized_step_joins_codes_inside_the_kernel(topo, kernels_on):
    """The gathered uint16 rows go straight into the Mosaic kernel: no
    widened (float32) or padded copy of them is made in HBM."""
    one = SingleDeviceSharding(topo.devices[0])
    table = _sds((DENSE_ROWS, DENSE_WIDTH), jnp.uint16, one)
    ids = _sds((DENSE_BATCH,), jnp.int32, one)
    text = eng._engine_fn_quantized.lower(
        table, ids, ids, use_pallas=True, sentinel=65535,
        scale=1.0).compile().as_text()
    assert "tpu_custom_call" in text
    assert "pad(" not in text and "convert(" not in text
    assert f"f32[{DENSE_BATCH}," not in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.uint16],
                         ids=["float32", "uint16"])
@pytest.mark.parametrize("shard_border", [False, True])
def test_sharded_step_compiles_on_4_chips(topo, kernels_on, shard_border,
                                          dtype):
    mesh = Mesh(np.array(topo.devices[:SHARDS]), ("edge",))
    assert mesh.devices.size == SHARDS
    quant = None if dtype == jnp.float32 else (65535, 1.0)
    fn = make_sharded_query_fn(mesh, "edge", use_pallas=True,
                               shard_border=shard_border, quant=quant)
    edge, rep = NamedSharding(mesh, P("edge")), NamedSharding(mesh, P())
    dpd = -(-(N // KMAX) // SHARDS)
    block = _sds((SHARDS * dpd * KMAX, WIDTH), dtype, edge)
    if shard_border:
        btable = _sds((-(-N // SHARDS) * SHARDS, Q_BORDERS), dtype, edge)
    else:
        btable = _sds((N, Q_BORDERS), dtype, rep)
    ids = _sds((BATCH,), jnp.int32, rep)
    text = fn.lower(block, btable, ids, ids, ids).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text     # the pmin that assembles the answers


def test_mesh4_cell_step_compiles_on_4_chips(topo, kernels_on):
    """The sharded step at the mesh4-trips cell's shapes and layout:
    uint16 codes, B row-sharded."""
    mesh = Mesh(np.array(topo.devices[:SHARDS]), ("edge",))
    fn = make_sharded_query_fn(mesh, "edge", use_pallas=True,
                               shard_border=True, quant=(65535, 1.0))
    edge, rep = NamedSharding(mesh, P("edge")), NamedSharding(mesh, P())
    block = _sds((SHARDS * MESH_DPD * KMAX, MESH_Q), jnp.uint16, edge)
    btable = _sds((MESH_N, MESH_Q), jnp.uint16, edge)
    ids = _sds((MESH_BATCH,), jnp.int32, rep)
    text = fn.lower(block, btable, ids, ids, ids).compile().as_text()
    assert text.startswith("HloModule jit__sharded_step")
    assert "tpu_custom_call" in text
    # the border-row assembly's pmin and the answers' pmin
    assert text.count(" all-reduce(") == 2
