"""The JAX spellings the rest of the codebase imports from one place.

Written for the installed jax 0.9.0: ``pltpu.CompilerParams``,
``jax.make_mesh(..., axis_types=...)``, ``jax.shard_map(check_vma=)`` and
``jax.tree.*``.  Importers use ``from repro.compat import ...``, so a JAX
upgrade that renames one of these is a one-file change.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "tpu_compiler_params",
    "make_mesh",
    "shard_map",
    "tree_map",
    "tree_leaves",
    "tree_flatten",
    "tree_unflatten",
]

tpu_compiler_params = pltpu.CompilerParams
shard_map = jax.shard_map
tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """jax.make_mesh with every axis Auto (GSPMD-propagated)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
