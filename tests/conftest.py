"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): "multi-node" is
emulated on a single host — the reference uses LocalCUDACluster
(raft_dask/test/test_comms.py:21); here XLA's host-platform device count
gives N fake devices so every sharded code path executes for real.
Must set env vars before the first jax import.

Sanitizer mode (``RAFT_TPU_SANITIZE=1``, docs/developer_guide.md): the
suite additionally runs under ``jax_numpy_rank_promotion="raise"`` and
``jax_debug_nans`` (the compute-sanitizer analog — RAFT's CI runs its
tests under exactly such a lane), and tests marked
``@pytest.mark.recompile_budget(n)`` assert their body triggers at most
``n`` backend compiles via the jax.monitoring jit-cache-miss counter —
an unexpected retrace fails the test instead of silently costing
seconds per call in production.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

# Persistent XLA compile cache: the suite is compile-bound on a 1-core
# CI box, and most of the wall clock is backend_compile of the same
# programs every run. A warm on-disk cache skips only the XLA compile —
# tracing still happens (span/comms counters are trace-time) and the
# recompile_budget listener counts backend compiles, so a cache hit can
# only relax an upper-bound budget, never break one. The directory
# follows JAX_COMPILATION_CACHE_DIR, else the repo's fixed .cache/jax.
from raft_tpu.core.compile_cache import use_compile_cache  # noqa: E402

if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from raft_tpu.obs import sanitize as _sanitize  # noqa: E402

if _sanitize.sanitize_enabled():
    _sanitize.apply_sanitize_config()
    # install before any compiles so budget deltas see every cache miss
    _sanitize.install_compile_counter()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _recompile_budget(request):
    """Enforce ``@pytest.mark.recompile_budget(n)`` in sanitizer mode.

    Outside sanitizer mode the marker is inert — budgets depend on a
    cold, deterministic jit cache, which only the dedicated
    ``RAFT_TPU_SANITIZE=1`` CI lane guarantees."""
    marker = request.node.get_closest_marker("recompile_budget")
    if marker is None or not _sanitize.sanitize_enabled():
        yield
        return
    with _sanitize.recompile_budget(int(marker.args[0]),
                                    what=request.node.nodeid):
        yield
