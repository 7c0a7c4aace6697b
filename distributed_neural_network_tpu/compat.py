"""The jax API surface the compiled paths are written against.

One installation exists (jax 0.9 with the native, vma-typed
``jax.shard_map``), so these are plain passthroughs: the step builders
keep one spelling of ``shard_map`` / ``axis_size``, and the checked-in
shardlint manifests keep the ``trace_mode`` field they were recorded
with.
"""

from __future__ import annotations

import jax


def trace_mode() -> str:
    """The ``trace_mode`` manifests record: always the native trace."""
    return "native"


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def axis_size(axis_name) -> int:
    """Static mesh-axis size inside shard_map (``jax.lax.axis_size``)."""
    return jax.lax.axis_size(axis_name)
