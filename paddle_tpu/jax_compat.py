"""The three jax entry points paddle_tpu reaches through one place.

Written for the installed jax (0.9.0): ``jax.shard_map`` with
``axis_names`` / ``check_vma``, ``jax.distributed.is_initialized`` and
``pltpu.CompilerParams``. Every call site in paddle_tpu goes through these
names (graftcheck's ``compat_shim`` rule holds them to it), so the next
jax upgrade is one file.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None):
    """``jax.shard_map``; ``axis_names`` are the manual mesh axes."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = frozenset(axis_names)
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def is_distributed_initialized() -> bool:
    """``jax.distributed.is_initialized()``."""
    return bool(jax.distributed.is_initialized())


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams``."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)
