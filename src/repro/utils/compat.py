"""The two JAX entry points the mesh programs and kernels share.

* ``shard_map(f, mesh=..., in_specs=..., out_specs=..., check_vma=...)``
  -- ``jax.shard_map``.
* ``shape_dtype_struct(shape, dtype, vma=None)`` -- a
  ``ShapeDtypeStruct`` carrying the varying manual axes of a kernel
  output launched under ``shard_map``.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "shape_dtype_struct"]

shard_map = jax.shard_map


def shape_dtype_struct(shape, dtype, vma=None):
    """``jax.ShapeDtypeStruct`` with optional varying manual axes."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
