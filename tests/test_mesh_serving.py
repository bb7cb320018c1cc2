"""Serving on a four-device edge mesh through the normal front door.

``DistanceService.submit`` with ``engine="auto"`` picks the sharded
engine on a 4-device backend; with B row-sharded and uint16 codes it
must answer 1024-trip batches bit for bit as the scalar loop and
Dijkstra do, and its counters (``shard.border_rows``,
``shard.max_owner_lanes``, ``kernel.join_codes``) must equal a recount
on the host from the same pairs. The mesh runs in a subprocess with
``--xla_force_host_platform_device_count=4``, so the main test session
keeps one CPU device.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
HARNESS = os.path.join(ROOT, "benchmarks", "chip")

DEVICES = 4
BATCH = 1024


def _mesh_case(batches: int = 2) -> dict:
    """Deploy a 3x3-district network (8x8 districts, border_links 5),
    serve ``batches`` trip batches, and return the answers, the
    references and the counters beside their host recount."""
    sys.path.insert(0, HARNESS)
    import jax
    from chipbench import bench, reference, traffic
    from repro import obs
    from repro.edge import EdgeSystem, ShardedBatchedEngine
    from repro.serve import ServingPolicy

    assert len(jax.devices()) == DEVICES
    net = bench.make_network({"grid": [3, 3], "district": [8, 8],
                              "border_links": 5, "weight_high": 15}, 2**33)
    g, part = bench.to_program(net)
    system = EdgeSystem.deploy(g, part)
    ss, ts = traffic.trip_pairs(net, batches * BATCH,
                                np.random.default_rng(7), origin_zipf=1.0,
                                same_district=0.6, hop_decay=0.5)
    ss, ts = ss.reshape(batches, BATCH), ts.reshape(batches, BATCH)
    # B is far under the auto thresholds at this size: the system's own
    # overrides pin what the auto-pick chooses at deployment size
    system.shard_border, system.label_dtype = True, "uint16"
    service = system.service(ServingPolicy(engine="auto"))
    obs.reset()
    got = np.stack([service.submit(s, t).distances for s, t in zip(ss, ts)])
    counted = obs.counters()
    engine = system.current_engine()
    layout = {"engine": type(engine).__name__,
              "border_sharded": engine.data.border_sharded,
              "dtype": str(engine.data.quant.dtype),
              "num_devices": engine.num_devices}

    # the quantized Pallas dispatches count too (interpret mode here)
    kernel = ShardedBatchedEngine(
        system.center.border_labels.table,
        [srv.augmented for srv in system.servers], part.assignment,
        use_pallas=True, shard_border=True, quant=engine.quant)
    obs.reset()
    kernel_got = np.asarray(kernel.query(ss[0], ts[0]))
    kernel_counted = obs.counters()

    # the host's recount: blocked placement, ceil(m / E) districts a device
    district = part.assignment
    dpd = -(-part.num_districts // DEVICES)
    cross = district[ss] != district[ts]
    owners = district[ss] // dpd
    recount = {
        "shard.border_rows": 2 * int(cross.sum()),
        "shard.max_owner_lanes": sum(
            int(np.bincount(o, minlength=DEVICES).max()) for o in owners)}
    ref = reference.Reference(net)
    want = np.empty(ss.shape, dtype=np.float32)
    for s in np.unique(ss):
        sel = ss == s
        want[sel] = ref.distances_from(int(s))[ts[sel]]
    return {"got": got, "loop": system.query_loop(ss.ravel(), ts.ravel()),
            "dijkstra": want, "kernel_got": kernel_got,
            "counted": counted, "kernel_counted": kernel_counted,
            "recount": recount, "layout": layout, "cross": int(cross.sum()),
            "batches": batches}


def _assert_mesh_case(r: dict) -> None:
    assert r["layout"] == {"engine": "ShardedBatchedEngine",
                           "border_sharded": True, "dtype": "uint16",
                           "num_devices": DEVICES}
    np.testing.assert_array_equal(r["got"].ravel(), r["loop"])
    np.testing.assert_array_equal(r["got"], r["dijkstra"])
    np.testing.assert_array_equal(r["kernel_got"], r["got"][0])
    assert 0 < r["cross"] < r["batches"] * BATCH
    for name, want in r["recount"].items():
        assert r["counted"][name] == want, name
    # the XLA path on the CPU widens no codes in a kernel
    assert "kernel.join_codes" not in r["counted"]
    assert r["kernel_counted"]["kernel.join_codes"] == 1
    assert r["counted"]["serve.pairs"] == r["batches"] * BATCH


def _run_under_4_devices(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={DEVICES}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=500,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_auto_mesh_serves_trip_batches_exactly_and_counts_them():
    out = _run_under_4_devices(
        "import tests.test_mesh_serving as m;"
        "m._assert_mesh_case(m._mesh_case());"
        "print('OK4')")
    assert "OK4" in out


def test_the_sharded_step_has_a_jit_name_of_its_own():
    import jax
    import jax.numpy as jnp
    from repro.edge import default_edge_mesh, make_sharded_query_fn
    mesh = default_edge_mesh(1)
    fn = make_sharded_query_fn(mesh, shard_border=True, quant=(65535, 1.0))
    ids = jax.ShapeDtypeStruct((256,), jnp.int32)
    text = fn.lower(jax.ShapeDtypeStruct((512, 300), jnp.uint16),
                    jax.ShapeDtypeStruct((576, 300), jnp.uint16),
                    ids, ids, ids).as_text(debug_info=True)
    assert "module @jit__sharded_step" in text
    assert "border_rows/" in text and "answer_pmin/" in text
