"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call, in the layout the program reads: each architecture's
``make_weights`` (``bench/arch/``) draws them with ``key_of`` and ``normal``.

Both the program and the plain reference are given these arrays; neither
makes its own.  The distribution is the benchmark's, chosen so that a
random model behaves like a trained one where the check needs it: logits of
order one (an embedding of standard deviation d_model**-0.5, tied to the
unembedding), so that greedy decoding wanders over the vocabulary instead
of repeating its input, and every projection at 1/sqrt(fan-in).  Norm
scales and biases are drawn around 1 and 0, so a fault in either shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def normal(key, shape, std, dtype):
    """``shape`` drawn from N(0, std**2) at float32, stored in ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def key_of(seed: int, *salt: int):
    """A JAX key for any whole ``seed``, however large."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    for v in (seed >> 32, *salt):
        key = jax.random.fold_in(key, v & 0xFFFFFFFF)
    return key


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has exactly the program's tree, shapes and
    dtypes (``program_shapes`` from ``jax.eval_shape`` of its init)."""
    mine = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    theirs = jax.tree.map(lambda x: (x.shape, str(x.dtype)), program_shapes)
    if mine != theirs:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"layout:\n{mine}\n!=\n{theirs}")
