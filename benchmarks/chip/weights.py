"""Seeded weights for a dense decoder, made on the device in one jitted
call, in the program's parameter layout and in float32, the type the
trainer holds them in.

Matrices, embeddings and biases are N(0, 0.02^2); norm scales are
1 + N(0, 0.02^2), so the scale and bias paths are exercised. Layers are
stacked on a leading axis, as the program's scan over layers expects.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def shapes(m: dict) -> dict:
    """The parameter tree of a dense decoder with sizes ``m``, as shapes."""
    L, d, ff, V = m["num_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]

    def lin(i, o, bias=False):
        p = {"w": (L, i, o)}
        if bias:
            p["b"] = (L, o)
        return p

    qkv_bias = m.get("qkv_bias", False)
    blocks = {"ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
              "attn": {"wq": lin(d, q, qkv_bias), "wk": lin(d, kv, qkv_bias),
                       "wv": lin(d, kv, qkv_bias), "wo": lin(q, d)},
              "ffn": {"up": lin(d, ff), "down": lin(ff, d)}}
    if m.get("activation", "silu") == "silu":
        blocks["ffn"]["gate"] = lin(d, ff)
    tree = {"embed": {"table": (V, d)}, "final_norm": {"scale": (d,)},
            "blocks": blocks}
    if not m.get("tie_embeddings", False):
        tree["lm_head"] = {"w": (d, V)}
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, frozen_tree):
    tree = jax.tree.unflatten(frozen_tree[0], frozen_tree[1])
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]
    out = []
    for i, (path, shape) in enumerate(paths):
        noise = STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
        is_scale = getattr(path[-1], "key", None) == "scale"
        out.append(1.0 + noise if is_scale else noise)
    return jax.tree.unflatten(frozen_tree[0], out)


def make(m: dict, key_seed: int):
    """The weights for sizes ``m`` from a 31-bit ``key_seed``."""
    leaves, treedef = jax.tree.flatten(shapes(m), is_leaf=_is_shape)
    return _make(jax.random.PRNGKey(key_seed), (treedef, tuple(leaves)))
