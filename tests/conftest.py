"""Test harness: 8 virtual CPU devices, the TPU-less mesh (SURVEY.md sec. 4).

The reference's only 'multi-node without a cluster' story was oversubscribing
one CPU with mpiexec (report sec. 2). Ours is
`--xla_force_host_platform_device_count=8`: the mesh, shard_map epochs,
masked pmean sync, and fault machinery all run under pytest with no TPU.

The platform is pinned through jax.config as well as the tier-1 command's
JAX_PLATFORMS=cpu, so a bare `pytest` on a machine with a chip never takes
it; XLA_FLAGS is honored because the CPU backend initializes lazily on first
use, which is after this conftest runs.
"""

import os

# `benchmark/tests`' cases take `BENCH` and `ROOT` from their conftest; the
# tier-1 suite imports some of those cases
# (tests/test_benchmark_contract.py), where `conftest` is this file.
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def n_devices():
    assert jax.device_count() == 8, (
        f"expected 8 forced CPU devices, got {jax.device_count()}"
    )
    return 8
