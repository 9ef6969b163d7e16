import os
import sys

# IMPORTANT: the test process runs on the single real CPU device and never
# sets XLA_FLAGS.  A test that needs a mesh of several devices describes a
# TPU topology (tests/test_tpu_compile.py) or starts a subprocess with its
# own flags.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:  # prefer the real property-testing engine when installed (CI does)
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    sys.path.insert(0, os.path.dirname(__file__))
    import _hypothesis_stub

    _hypothesis_stub.install()

import numpy as np
import pytest


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described TPU v5e 2x2 topology: four devices to compile for, with
    no chip attached.  Described inside a fixture, never at import: only one
    process at a time may load the TPU library, and every test worker
    imports the test files."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
