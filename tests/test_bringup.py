"""The bring-up contract off the chip: where the compile cache goes, and
that `chip_smoke.py` refuses to run anywhere but on a TPU."""

import os
import subprocess
import sys

import jax
import pytest

from distributed_neural_network_tpu import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the jax cache settings `enable_compile_cache` writes."""
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    # a known starting point: whatever an earlier test's entry point set
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


@pytest.mark.parametrize(
    "env_dir, flag_dir, want, config_written",
    [
        # the variable wins, and no directory is set in code
        ("/from/env", None, "/from/env", False),
        ("/from/env", "/from/flag", "/from/env", False),
        # unset: the flag, else the fixed checkout-relative default
        (None, "/from/flag", "/from/flag", True),
        (None, None, os.path.join(REPO, ".jax_cache"), True),
    ],
    ids=["env", "env-beats-flag", "flag", "default"],
)
def test_compile_cache_placement(monkeypatch, cache_config, env_dir,
                                 flag_dir, want, config_written):
    if env_dir is None:
        monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(runtime.CACHE_ENV, env_dir)
    assert runtime.enable_compile_cache(flag_dir) == want
    assert jax.config.jax_compilation_cache_dir == (
        want if config_written else None
    )
    # small programs (CNN epochs, serve buckets) must cache too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
