"""Test harness: force an 8-device virtual CPU mesh BEFORE jax import.

This is the analogue of the reference's fake `custom_cpu` plugin device used
to test the runtime without hardware (SURVEY.md §4: test/custom_runtime/) and
of its single-node multi-proc distributed tests — sharding/collective tests
run on 8 virtual CPU devices.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = flags + " --xla_force_host_platform_device_count=8"
# On a starved host (1-2 cores), XLA CPU's multi-threaded Eigen kernels
# segfault/abort under the 8-virtual-device oversubscription (hybrid-mesh
# collectives in test_clip_dispatch et al die inside the runtime). Force
# single-threaded Eigen there — slower, but the suite completes.
if (os.cpu_count() or 1) <= 2 and "xla_cpu_multi_thread_eigen" not in flags:
    flags = flags + " --xla_cpu_multi_thread_eigen=false"
os.environ["XLA_FLAGS"] = flags

# Persistent compilation cache: repeated suite runs (and xdist workers hitting
# identical programs) reuse compiled executables instead of re-running XLA —
# the suite is dominated by 8-device mesh compiles.
import pytest  # noqa: E402

from paddle_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()


@pytest.fixture
def uninstall_mesh():
    """For tests that build a trainer: it installs its mesh process-wide, and
    left behind, that mesh makes the single-device engines of later test
    files retrace on a resharded KV pool."""
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    yield
    set_hybrid_communicate_group(None)
