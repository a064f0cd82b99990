"""Seeded weights, made on the device in one jitted call, in the
program's own parameter tree for a ``ModelConfig`` and in the type of
each of its leaves (float32, the type the trainer holds them in).

Every leaf is N(0, 0.02^2), drawn from ``fold_in(key, i)`` for the i-th
leaf in flattened order; norm scales are 1 + N(0, 0.02^2), so the scale
and bias paths are exercised. Only the tree's shapes and types are taken
from the program (``jax.eval_shape`` of its ``init_params``), never its
values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import init_params

STD = 0.02


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, treedef, specs):
    out = []
    for i, (shape, dtype, is_scale) in enumerate(specs):
        noise = STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
        out.append((1.0 + noise if is_scale else noise).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def make(cfg, key_seed: int):
    """The weights of ``ModelConfig`` ``cfg`` from a 31-bit ``key_seed``."""
    tree = jax.eval_shape(functools.partial(init_params, cfg=cfg),
                          jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = tuple((leaf.shape, leaf.dtype,
                   getattr(path[-1], "key", None) == "scale")
                  for path, leaf in flat)
    return _make(jax.random.PRNGKey(key_seed), treedef, specs)
