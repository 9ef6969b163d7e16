"""The on-chip benchmark: ``run.py`` runs one cell once, ``calibrate.py``
takes the readings that the limits of ``correct`` are set from."""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache at a fixed directory inside the
    checkout, so that only a cell's first run there compiles.  Call before
    JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def tpu_devices():
    """JAX's devices; exits with an error unless the default one is a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench: JAX's default device is {devices[0].platform!r}, not a TPU")
    return devices
