"""The handful of jax names the package reaches through one module, as
the installed JAX (0.9.0) spells them: ``jax.shard_map`` (with
``check_vma``), ``jax.lax.pcast(..., to="varying")``,
``jax.lax.axis_size``, ``jax.distributed.is_initialized``."""
from __future__ import annotations

import jax
from jax import shard_map  # noqa: F401
from jax.distributed import is_initialized as distributed_is_initialized  # noqa: F401
from jax.lax import axis_size  # noqa: F401


def pvary(x, axes):
    """Mark ``x`` device-varying over ``axes`` inside a ``shard_map``."""
    return jax.lax.pcast(x, tuple(axes), to="varying")
