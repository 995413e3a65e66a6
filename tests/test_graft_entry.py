"""Driver-gate regression tests.

The multichip dryrun is a CPU-mesh correctness check. The device count of
the virtual CPU pool is fixed when the backend starts, so `dryrun_multichip`
always re-execs into a child whose environment asks for the pool it needs.
Analogue of the reference's fake custom_cpu plugin CI device (SURVEY §4,
test/custom_runtime/).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENTRY = os.path.join(REPO, "__graft_entry__.py")


@pytest.mark.slow
def test_dryrun_multichip_topology_matrix():
    env = dict(os.environ)
    env.pop("PADDLE_TPU_DRYRUN_CASES", None)  # stray selector would skip cases
    out = subprocess.run(
        [sys.executable, ENTRY, "dryrun", "8"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    # the full topology matrix must be green (3-step loss-sequence parity)
    for topo in ("dp8", "dp2xmp4", "pp2xmp2xsharding2", "ep4_moe", "sp8_ring"):
        assert f"{topo}: " in out.stdout and "MISMATCH" not in out.stdout, \
            out.stdout[-2000:]


def test_child_env_forces_the_cpu_pool():
    import __graft_entry__ as g

    env = g._cpu_mesh_env(8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert REPO in env["PYTHONPATH"].split(os.pathsep)
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert env[g._CHILD_MARKER] == "1"
