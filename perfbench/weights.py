"""Weights of a configuration, made on the device from the seed.

One jitted call builds every float32 leaf of the parameter tree that the
configuration's architecture module describes (``arch.load(cfg)``: its
``shapes`` give the tree, in the layout the program's serving entry point
takes, and its ``leaf`` each leaf's initial values), with one PRNG key a
leaf, split from the seed's key in the tree's flattened order.  The
reference calls the same function after the window, so the two sides
start from the same numbers without either handing the other anything.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

import arch


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(mod, sizes, key):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        mod.shapes(dict(sizes)), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        names = [p.key for p in path if hasattr(p, "key")]
        out.append(mod.leaf(k, names, shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def key_of(seed: int):
    """The PRNG key of a seed of any size."""
    return jax.random.key(int(np.random.default_rng(seed).integers(2**32)))


def make(cfg: dict, seed: int) -> dict:
    """The float32 parameter tree of ``cfg`` for ``seed``."""
    sizes = tuple((k, v) for k, v in cfg.items()
                  if isinstance(v, (bool, int, float, str)))
    return _make(arch.load(cfg), sizes, key_of(seed))
