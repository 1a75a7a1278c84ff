"""Test env: force CPU with 8 virtual devices so multi-chip sharding
(tp/pp/dp/sp meshes) is exercised without TPU hardware. Must run before the
first `import jax` anywhere in the test process."""

import os

# tests run on the virtual 8-device CPU mesh whatever the environment names
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
# entry points turn the persistent compilation cache on (cli.make_engine);
# tests count compiles and must not find another run's programs on disk
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop every compiled program when a test module ends. One process runs
    the whole suite and XLA:CPU never frees an executable by itself: with
    thousands of them resident the tier-1 run died of a segmentation fault
    inside the compiler four fifths of the way through (tests/test_server.py's
    batched fixture); released per module, the same tree runs to its end."""
    yield
    jax.clear_caches()
