"""The one spelling of fast-moving JAX APIs, for src *and* tests.

Written for the installed JAX 0.9 line (``requirements.txt``); when JAX
moves an API again, this is the only file that chases it.
"""
from __future__ import annotations

from jax import shard_map  # noqa: F401


def abstract_mesh(shape, axis_names):
    """``jax.sharding.AbstractMesh`` of ``shape`` over ``axis_names``.

    Spec math on an AbstractMesh needs no device allocation, so production
    geometries (16x16, 2x16x16) are testable on a single CPU.
    """
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axis_names))
