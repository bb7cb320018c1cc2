"""The steady-state engine layout as a table: ``choose_layout`` maps the
policy's three knobs and what the system observes (device count, B's
float32 bytes, the index's float32 bytes, whether the fitted uint16
spec is lossless) to sharded / B row-sharded / storage dtype.

Columns: engine, shard_border, label_dtype, devices, B bytes, index
bytes, lossless -> sharded, B row-sharded, dtype.
"""
import pytest

from repro.edge.router import (QUANT_AUTO_BYTES, SHARD_BORDER_AUTO_BYTES,
                               EngineLayout, choose_layout)

SB, QA = SHARD_BORDER_AUTO_BYTES, QUANT_AUTO_BYTES
F32, U16, I16 = "float32", "uint16", "int16"


def _index_bytes(n: int, q: int, districts: int, k: int = 256) -> int:
    """B plus one dense (k, k) table per district, in float32."""
    return 4 * (n * q + districts * k * k)


AUTO_GRID = [
    # devices x B bytes {at, just above} x index bytes {at, just above}
    # x fitted spec {lossless, lossy}, every knob on auto
    ("auto", None, "auto", 1, SB, QA, True, False, False, F32),
    ("auto", None, "auto", 1, SB, QA, False, False, False, F32),
    ("auto", None, "auto", 1, SB, QA + 4, True, False, False, U16),
    ("auto", None, "auto", 1, SB, QA + 4, False, False, False, F32),
    ("auto", None, "auto", 1, SB + 4, QA, True, False, False, F32),
    ("auto", None, "auto", 1, SB + 4, QA, False, False, False, F32),
    ("auto", None, "auto", 1, SB + 4, QA + 4, True, False, False, U16),
    ("auto", None, "auto", 1, SB + 4, QA + 4, False, False, False, F32),
    ("auto", None, "auto", 4, SB, QA, True, True, False, F32),
    ("auto", None, "auto", 4, SB, QA, False, True, False, F32),
    ("auto", None, "auto", 4, SB, QA + 4, True, True, False, U16),
    ("auto", None, "auto", 4, SB, QA + 4, False, True, False, F32),
    ("auto", None, "auto", 4, SB + 4, QA, True, True, True, F32),
    ("auto", None, "auto", 4, SB + 4, QA, False, True, True, F32),
    ("auto", None, "auto", 4, SB + 4, QA + 4, True, True, True, U16),
    ("auto", None, "auto", 4, SB + 4, QA + 4, False, True, True, F32),
]

EXPLICIT_ENGINE = [
    # engine x shard_border x devices, B just above its threshold
    ("replicated", None, "auto", 1, SB + 4, QA, True, False, False, F32),
    ("replicated", None, "auto", 4, SB + 4, QA, True, False, False, F32),
    ("replicated", False, "auto", 1, SB + 4, QA, True, False, False, F32),
    ("replicated", False, "auto", 4, SB + 4, QA, True, False, False, F32),
    ("replicated", True, "auto", 1, SB + 4, QA, True, False, False, F32),
    ("replicated", True, "auto", 4, SB + 4, QA, True, False, False, F32),
    ("sharded", None, "auto", 1, SB + 4, QA, True, True, True, F32),
    ("sharded", None, "auto", 4, SB + 4, QA, True, True, True, F32),
    ("sharded", False, "auto", 1, SB + 4, QA, True, True, False, F32),
    ("sharded", False, "auto", 4, SB + 4, QA, True, True, False, F32),
    ("sharded", True, "auto", 1, SB + 4, QA, True, True, True, F32),
    ("sharded", True, "auto", 4, SB + 4, QA, True, True, True, F32),
]

EXPLICIT_DTYPE = [
    # an explicit dtype is honored whatever the fit
    ("auto", None, F32, 1, 4096, QA + 4, True, False, False, F32),
    ("auto", None, F32, 1, 4096, QA + 4, False, False, False, F32),
    ("auto", None, U16, 1, 4096, QA + 4, True, False, False, U16),
    ("auto", None, U16, 1, 4096, QA + 4, False, False, False, U16),
    ("auto", None, I16, 1, 4096, QA + 4, True, False, False, I16),
    ("auto", None, I16, 1, 4096, QA + 4, False, False, False, I16),
]

DEPLOYMENTS = [
    # the benchmark's configurations, from their sizes and policies
    pytest.param("replicated", None, "auto", 1, 4 * 16384 * 446,
                 _index_bytes(16384, 446, 64), True, False, False, U16,
                 id="sparse-8x8"),
    pytest.param("replicated", None, "auto", 1, 4 * 9216 * 928,
                 _index_bytes(9216, 928, 36), True, False, False, U16,
                 id="dense-6x6"),
    pytest.param("auto", None, "auto", 4, 4 * 16384 * 1100,
                 _index_bytes(16384, 1100, 64), True, True, True, U16,
                 id="mesh4-8x8"),
]


@pytest.mark.parametrize(
    "engine,shard_border,label_dtype,devices,border_bytes,index_bytes,"
    "lossless,sharded,border_sharded,dtype",
    AUTO_GRID + EXPLICIT_ENGINE + EXPLICIT_DTYPE + DEPLOYMENTS)
def test_choose_layout_table(engine, shard_border, label_dtype, devices,
                             border_bytes, index_bytes, lossless, sharded,
                             border_sharded, dtype):
    assert choose_layout(engine, shard_border, label_dtype, devices,
                         border_bytes, index_bytes, lossless) \
        == EngineLayout(sharded, border_sharded, dtype)
