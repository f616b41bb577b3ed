"""Seeded weights of any layout (a family's ``layout``).

The benchmark makes the weights itself, on the device, in one jitted call
from the seed, in the type they are served in (bf16). The same call with
the same seed makes the same bits, so the reference, which may take
nothing the program has made, draws them again after the window.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

MATRIX_STD = 0.02     # every projection, expert, router, embedding, head
NORM_JITTER = 0.1     # RMSNorm weights are 1 + NORM_JITTER * normal


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, all its bits used."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.lru_cache(maxsize=None)
def _maker(treedef, leaves):
    def make(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            w = 1.0 + NORM_JITTER * z if kind == "norm" else MATRIX_STD * z
            out.append(w.astype(jnp.bfloat16))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)


def make(specs: Dict, seed: int):
    """bf16 weights of the layout ``specs`` (shape, "norm" | "matrix") per
    leaf, drawn from ``seed``, on the device. Leaf i, in the tree's order,
    is drawn from ``fold_in(key, i)``."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    return _maker(treedef, tuple(leaves))(key_of(seed))
