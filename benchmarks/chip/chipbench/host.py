"""What every entry point under ``benchmarks/chip`` does first: import
paths, the compile cache, and the refusal to run off the chip."""
from __future__ import annotations

import os
import sys

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HARNESS))


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def setup_environment() -> None:
    """Put the program on the import path, keep libtpu's logs off /tmp,
    and pin the compile cache: JAX reads ``JAX_COMPILATION_CACHE_DIR``
    itself; only without it is the cache a fixed directory inside the
    checkout."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # cache every program, so that a checkout's second run compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int) -> None:
    """Exit non-zero unless JAX sees a TPU with at least ``chips``
    chips."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"chipbench: no TPU found (platform {d0.platform!r}); "
                 "this benchmark runs only on the chip")
    if len(devices) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, found "
                 f"{len(devices)}")
    log(f"device platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
