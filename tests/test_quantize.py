"""Quantized label storage invariants (core/quantize + dtype threading).

Property layer: lossless round-trip on integral weights, the +inf
sentinel, the ``is_lossless_for`` predicate, and bitwise f32/uint16
join parity — under ``hypothesis`` when available, over a seeded
parametrization otherwise (same convention as test_core_properties).

Engine layer: every serving layout (replicated, district-sharded,
B-sharded) must answer bit-for-bit identically in
uint16 and float32 on mixed-rule batches; the 8-device case re-runs the
same builder in a subprocess with XLA_FLAGS (pattern from
test_sharded_oracle).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.quantize import (LABEL_DTYPES, QuantSpec, dtype_name,
                                 fit_label_spec, sentinel_of)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:          # clean env: seeded fallback below
    HAVE_HYPOTHESIS = False

SETTINGS = dict(max_examples=25, deadline=None)
FALLBACK_SEEDS = list(range(1, 13))


def _random_table(seed: int, dtype=np.uint16) -> np.ndarray:
    """Random label-table-shaped array: non-negative integral values in
    the dtype's lossless range with a sprinkle of +inf (unreachable)."""
    rng = np.random.default_rng(seed)
    shape = (int(rng.integers(1, 40)), int(rng.integers(1, 12)))
    hi = sentinel_of(dtype) - 1
    t = rng.integers(0, hi + 1, size=shape).astype(np.float32)
    t[rng.random(shape) < 0.15] = np.inf
    return t


# -- properties (plain functions, framework-agnostic) -----------------------

def _check_roundtrip(seed: int, dtype) -> None:
    t = _random_table(seed, dtype)
    spec = QuantSpec.fit(t, dtype=dtype)
    assert spec.lossless and spec.scale == 1.0
    assert spec.is_lossless_for(t)
    back = spec.dequantize(spec.quantize(t))
    assert np.array_equal(back, t)           # exact, including +inf


def _check_sentinel(seed: int, dtype) -> None:
    t = _random_table(seed, dtype)
    spec = QuantSpec.fit(t, dtype=dtype)
    codes = spec.quantize(t)
    assert codes.dtype == np.dtype(dtype)
    assert np.array_equal(codes == spec.sentinel, ~np.isfinite(t))
    assert np.isposinf(spec.dequantize(
        np.array([spec.sentinel], dtype=dtype)))[0]


def _check_join_parity(seed: int, dtype) -> None:
    """min-plus join on codes == join on float32, bitwise, both device
    paths (pallas-interpret and the XLA int32 accumulate)."""
    from repro.kernels.label_join import ops as lj
    t = _random_table(seed, dtype)
    spec = QuantSpec.fit(t, dtype=dtype)
    codes = spec.quantize(t)
    rng = np.random.default_rng(seed + 99)
    k = int(rng.integers(1, 20))
    ss = rng.integers(0, t.shape[0], size=k)
    ts = rng.integers(0, t.shape[0], size=k)
    ref = lj.join_gathered(t, ss, ts)
    sent, scale = spec.key()
    for use_pallas in (True, False):
        got = lj.join_quantized_gathered(codes, ss, ts, sentinel=sent,
                                         scale=scale,
                                         use_pallas=use_pallas)
        assert np.array_equal(ref, got), (seed, use_pallas)


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 10_000), st.sampled_from([np.uint16, np.int16]))
    @settings(**SETTINGS)
    def test_roundtrip_lossless(seed, dtype):
        _check_roundtrip(seed, dtype)

    @given(st.integers(0, 10_000), st.sampled_from([np.uint16, np.int16]))
    @settings(**SETTINGS)
    def test_sentinel_marks_unreachable(seed, dtype):
        _check_sentinel(seed, dtype)

    @given(st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_join_parity(seed):
        _check_join_parity(seed, np.uint16)
else:
    @pytest.mark.parametrize("seed", FALLBACK_SEEDS)
    @pytest.mark.parametrize("dtype", [np.uint16, np.int16])
    def test_roundtrip_lossless(seed, dtype):
        _check_roundtrip(seed, dtype)

    @pytest.mark.parametrize("seed", FALLBACK_SEEDS)
    @pytest.mark.parametrize("dtype", [np.uint16, np.int16])
    def test_sentinel_marks_unreachable(seed, dtype):
        _check_sentinel(seed, dtype)

    @pytest.mark.parametrize("seed", FALLBACK_SEEDS[:6])
    def test_join_parity(seed):
        _check_join_parity(seed, np.uint16)


# -- spec mechanics ---------------------------------------------------------

def test_fit_lossy_when_range_exceeded():
    t = np.array([[0.0, 2.0 * sentinel_of(np.uint16)]], dtype=np.float32)
    spec = QuantSpec.fit(t)
    assert not spec.lossless and spec.scale > 1.0
    # lossy spec still keeps the ordering and the sentinel
    codes = spec.quantize(t)
    assert codes[0, 0] < codes[0, 1] < spec.sentinel


def test_fractional_weights_are_lossy():
    t = np.array([[0.1, 0.2, 0.3]], dtype=np.float32)
    spec = QuantSpec(scale=1.0, dtype=np.uint16, lossless=False)
    assert not spec.is_lossless_for(t)
    assert QuantSpec.fit(t).is_lossless_for(t) is False


def test_fit_label_spec_spans_all_tables():
    from repro.core import (build_all_local_indexes,
                            build_border_labels_hierarchical)
    from repro.ingest import synthetic_continent
    csr, part = synthetic_continent(grid=(2, 2), district=(6, 6), seed=2)
    g = csr.to_graph()
    bl = build_border_labels_hierarchical(g, part)
    locals_ = build_all_local_indexes(g, part, bl=bl)
    spec = fit_label_spec(bl.table, locals_)
    assert spec.lossless                      # integer-ish grid weights
    for li in locals_:
        assert spec.is_lossless_for(li.dense_table())


def test_dtype_name_and_registry():
    assert dtype_name(np.uint16) == "uint16"
    assert {"uint16", "int16"} <= set(LABEL_DTYPES)
    assert sentinel_of(np.uint16) == np.iinfo(np.uint16).max
    assert sentinel_of(np.int16) == np.iinfo(np.int16).max


# -- serving layouts: uint16 == float32 bit-for-bit -------------------------

def _layout_case():
    """All three layouts x {float32, uint16} on one mixed-rule batch.
    Shared by the in-process (1-device) test and the 8-device
    subprocess."""
    from repro.edge import (BatchedQueryEngine, EdgeSystem,
                            ShardedBatchedEngine)
    from repro.ingest import synthetic_continent

    # integral weights (U{1..15}) so the fitted spec is lossless and the
    # bitwise-parity guarantee applies
    csr, part = synthetic_continent(grid=(2, 4), district=(6, 6), seed=5)
    g = csr.to_graph()
    system = EdgeSystem.deploy(g, part)
    rng = np.random.default_rng(3)
    ss = rng.integers(0, g.num_vertices, size=500)
    ts = rng.integers(0, g.num_vertices, size=500)
    ss[::13] = ts[::13]
    args = (system.center.border_labels.table,
            [srv.augmented for srv in system.servers],
            part.assignment)
    spec = fit_label_spec(args[0], args[1])
    assert spec.lossless
    out = {"ref": np.asarray(system.query_loop(ss, ts)), "bytes": {}}
    for tag, quant in (("f32", None), ("u16", spec)):
        rep = BatchedQueryEngine(*args, quant=quant)
        shard = ShardedBatchedEngine(*args, quant=quant)
        bshard = ShardedBatchedEngine(*args, shard_border=True,
                                      quant=quant)
        out[tag] = {
            "rep": np.asarray(rep.query(ss, ts)),
            "shard": np.asarray(shard.query(ss, ts)),
            "bshard": np.asarray(bshard.query(ss, ts)),
        }
        out["bytes"][tag] = {
            "rep": rep.size_bytes(),
            "shard": shard.size_bytes(),
            "bshard": bshard.size_bytes(),
        }
    return out


def _assert_layout_case(r) -> None:
    for tag in ("f32", "u16"):
        for layout, got in r[tag].items():
            np.testing.assert_array_equal(
                got, r["ref"], err_msg=f"{layout}/{tag}")
    for layout in ("rep", "shard", "bshard"):
        f32b, u16b = r["bytes"]["f32"][layout], r["bytes"]["u16"][layout]
        assert u16b <= 0.55 * f32b, (layout, u16b, f32b)


def test_all_layouts_bitwise_parity_and_bytes():
    _assert_layout_case(_layout_case())


@pytest.mark.slow
def test_all_layouts_parity_eight_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    code = ("import jax; assert len(jax.devices()) == 8;"
            "import tests.test_quantize as m;"
            "m._assert_layout_case(m._layout_case());"
            "print('OK8')")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=500,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK8" in out.stdout


# -- policy / router surface ------------------------------------------------

def test_serving_policy_label_dtype_validation():
    from repro.serve.service import ServingPolicy
    ServingPolicy(label_dtype="uint16")       # fine
    with pytest.raises(ValueError, match="label_dtype"):
        ServingPolicy(label_dtype="uint8")


def test_auto_dtype_small_system_stays_float32():
    """Auto never changes an answer: below the byte threshold the
    layout stores float32 and the resolved quant is None, so existing
    float32 tests stay bitwise identical."""
    from repro.core import bfs_grow_partition, grid_road_network
    from repro.edge import EdgeSystem
    from repro.serve.service import ServingPolicy
    g = grid_road_network(6, 6, seed=0)
    part = bfs_grow_partition(g, 4, seed=0)
    system = EdgeSystem.deploy(g, part)
    assert system.layout().label_dtype == "float32"
    assert system.layout(ServingPolicy(label_dtype="auto")) \
        .label_dtype == "float32"
    assert system._resolve_quant("float32") is None
    explicit = system.layout(ServingPolicy(label_dtype="uint16"))
    assert explicit.label_dtype == "uint16"   # explicit: always honored
    spec = system._resolve_quant(explicit.label_dtype)
    assert spec is not None and spec.dtype == np.dtype(np.uint16)


def test_service_explicit_uint16_matches_float32():
    from repro.edge import EdgeSystem
    from repro.ingest import synthetic_continent
    from repro.serve.service import ServingPolicy
    csr, part = synthetic_continent(grid=(2, 2), district=(6, 6), seed=11)
    g = csr.to_graph()
    system = EdgeSystem.deploy(g, part)
    rng = np.random.default_rng(9)
    ss = rng.integers(0, g.num_vertices, size=300)
    ts = rng.integers(0, g.num_vertices, size=300)
    ref = system.service(ServingPolicy(label_dtype="float32")) \
        .submit(ss, ts).distances
    for placement in ("replicated", "sharded"):
        got = system.service(ServingPolicy(engine=placement,
                                           label_dtype="uint16")) \
            .submit(ss, ts).distances
        np.testing.assert_array_equal(got, ref, err_msg=placement)
