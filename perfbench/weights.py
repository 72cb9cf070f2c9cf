"""Weights of a configuration, made on the device from the seed.

One jitted call builds every float32 leaf, in the parameter layout the
program's serving entry point takes (``prepare_params`` quantizes them):
the embedding (tied head), the final norm gain, and one stacked unit of
layers, each leaf with a leading layer axis.  Dense weights are
N(0, 1) / sqrt(fan-in), the embedding N(0, 1) * 0.02, norm gains
1 + 0.1 N(0, 1).  The reference calls the same function after the
window, so the two sides start from the same numbers without either
handing the other anything.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the parameter tree, for the configuration file's
    sizes."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    attn = {"wq": (L, d, h * hd), "wk": (L, d, kv * hd),
            "wv": (L, d, kv * hd), "wo": (L, h * hd, d)}
    if cfg["qk_norm"]:
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    if cfg["hidden_act"] == "silu":
        mlp = {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
    else:
        mlp = {"w_up": (L, d, f), "w_down": (L, f, d)}
    return {"embed": (v, d), "final_norm": (d,),
            "units": [{"norm1": (L, d), "attn": attn, "norm2": (L, d),
                       "mlp": mlp}]}


def _leaf(key, path, shape):
    name = path[-1]
    if name == "embed":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    if name.startswith("w"):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(shape[-2]))
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


_SIZE_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "qk_norm", "hidden_act")


@functools.partial(jax.jit, static_argnums=0)
def _make(sizes, key):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(dict(sizes)), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        names = [p.key for p in path if hasattr(p, "key")]
        out.append(_leaf(k, names, shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def key_of(seed: int):
    """The PRNG key of a seed of any size."""
    return jax.random.key(int(np.random.default_rng(seed).integers(2**32)))


def make(cfg: dict, seed: int) -> dict:
    """The float32 parameter tree of ``cfg`` for ``seed``."""
    return _make(tuple((k, cfg[k]) for k in _SIZE_KEYS), key_of(seed))
