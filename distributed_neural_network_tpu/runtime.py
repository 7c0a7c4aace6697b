"""Where the program runs: the backend decision and the compile cache.

Two facts every kernel route and every entry point needs, each decided in
exactly one place:

- ``on_tpu()`` - whether compiled Mosaic kernels can run. The CPU
  behaviour (plain-XLA attention, jnp classifier head, interpreted decode
  kernel) exists for the tests; ``route()`` names which side a run took so
  an entry point can print it and a run meant for the chip cannot fall back
  silently.
- ``enable_compile_cache()`` - JAX's persistent compilation cache, placed
  from outside. ``JAX_COMPILATION_CACHE_DIR`` wins and no directory is then
  set in code; otherwise the fixed ``<checkout>/.jax_cache`` (the path is
  part of the cache key, so it never carries a pid, a timestamp or a temp
  name). Only entry points call it: importing the library leaves the cache
  off.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def on_tpu() -> bool:
    """True when the default backend compiles Mosaic kernels."""
    return jax.default_backend() == "tpu"


def route() -> str:
    """'pallas' where the kernels run compiled, 'xla' where the plain
    path stands in for them (the label entry points print)."""
    return "pallas" if on_tpu() else "xla"


def enable_compile_cache(flag_dir: str | None = None) -> str:
    """Switch the persistent compilation cache on and return its directory.

    ``flag_dir`` (an entry point's ``--compilation-cache-dir``) is honoured
    only while the environment variable is unset. The compile-time and
    entry-size floors are zeroed so the small programs (CNN epochs, serve
    buckets) cache too - a chip call starts on a fresh machine, and their
    sum is most of a cold run.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = flag_dir or DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
