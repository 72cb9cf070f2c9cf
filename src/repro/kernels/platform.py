"""Every platform decision of the repo, in one module.

The same program runs on a TPU (where users serve it) and on the CPU
(where tests run).  What differs between the two is decided here and
nowhere else:

  * which lowering each op uses (``lowering``): a Pallas kernel or its
    blocked-XLA twin in ``kernels/ref.py``;
  * whether a Pallas kernel runs compiled or in interpret mode
    (``pallas_interpret``): interpret mode only on the CPU backend, and
    a kernel the TPU compiler refuses raises instead of interpreting;
  * the dtype the delta tables are stored in (``delta_table_dtype``),
    which follows the qdot lowering that reads them;
  * whether a jitted serve step donates its decode state (``donate``);
  * the Pallas compiler parameters every ``pallas_call`` passes
    (``compiler_params``);
  * where the persistent compile cache lives (``enable_compile_cache``,
    called by the entry points only).

On the TPU the approximate qdot runs ``approx_matmul.onehot_qdot``: the
activations select their rows of the product table with a one-hot
contraction on the MXU, and each weight is looked up in its row with a
lane gather (``jnp.take_along_axis`` along the lanes of an (8, 128)
int32 tile, which Mosaic, the TPU Pallas compiler, lowers to the vreg's
lane permute).  That is the one gather Mosaic lowers: 2-D, within one
vreg along the gather axis.  The older kernels ``delta_matmul``,
``lut_matmul`` and ``residual_matmul`` gather per element from a whole
table, which it does not: it has no lowering for the ``dynamic_slice``
that walks their K-subtiles, and cannot gather from a flat
65,536-entry table.  Off the TPU the qdot runs its blocked-XLA twin
(``ref.fused_qdot_ref``), the kernel's oracle; the integer ``delta``
backend runs ``ref.delta_matmul_ref`` everywhere.  The decode-attention
kernel is made of 2-D dots and elementwise math, which Mosaic lowers,
so the TPU runs it as a Pallas kernel.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# Pallas kernels the TPU compiler refuses, with the reason.  An explicit
# request for one of them on a TPU raises this reason.
_REFUSED_ON_TPU = {
    "delta_matmul": "the stage-2 delta gather does not lower in Mosaic: no "
                    "dynamic_slice lowering for the K-subtile walk, and no "
                    "gather from the flat 65,536-entry table (the qdot's "
                    "kernel, onehot_qdot, gathers within one vreg only)",
    "lut_matmul": "the per-k product-LUT gather does not lower in Mosaic "
                  "(only 2-D gathers within one vreg are supported)",
    "residual_matmul": "the factor-table jnp.take fails Mosaic's gather "
                       "shape check",
}

# The lowering 'auto' picks for each op, per platform; every platform
# not listed (and every op not listed) uses the XLA twin.
_AUTO = {
    "tpu": {"qdot": "pallas", "decode_attention": "pallas"},
}

OPS = ("qdot", "decode_attention")


def backend() -> str:
    """The platform JAX runs on ('cpu', 'tpu', 'gpu')."""
    return jax.default_backend()


def lowering(op: str, requested: str = "auto") -> str:
    """The lowering ``op`` ('qdot' or 'decode_attention') uses:
    'pallas' or 'xla'.  'auto' picks by platform; an explicit request is
    returned as is (the Pallas kernel itself refuses what the platform
    cannot build — see ``pallas_interpret``)."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; one of {OPS}")
    if requested == "auto":
        return _AUTO.get(backend(), {}).get(op, "xla")
    if requested not in ("pallas", "xla"):
        raise ValueError(f"unknown lowering {requested!r}; expected "
                         f"'auto', 'pallas' or 'xla'")
    return requested


def pallas_interpret(kernel: str) -> bool:
    """The ``interpret`` flag of ``kernel``'s pallas_call: True on the
    CPU backend (interpret mode is how the tests run kernels there),
    False on a TPU.  A kernel the TPU compiler refuses raises there,
    naming the reason; other platforms have no Pallas path here."""
    b = backend()
    if b == "cpu":
        return True
    if b == "tpu":
        if kernel in _REFUSED_ON_TPU:
            raise NotImplementedError(
                f"Pallas kernel {kernel!r} does not build for the TPU: "
                f"{_REFUSED_ON_TPU[kernel]}.  Use lowering='xla' (what "
                f"'auto' picks on the TPU).")
        return False
    raise NotImplementedError(
        f"Pallas kernel {kernel!r} has no lowering for platform {b!r}; "
        f"use lowering='xla'")


def delta_table_dtype():
    """Storage dtype of installed delta tables (calib.plan banks): the
    XLA twins gather from an int32 view, so the tables are pre-widened
    to int32 where the qdot runs the twin (a traced int16 table would
    cost a 64Ki-element convert per layer per step); the qdot kernel
    widens the one table it uses into its product planes, so there the
    tables keep their built form (None, int16)."""
    return jnp.int32 if lowering("qdot") == "xla" else None


def donate(*argnums: int) -> tuple:
    """``donate_argnums`` for a jitted step's decode state: donated on
    the TPU (the KV caches update in place, and at model scale the
    state is the memory budget); kept on the CPU, where donation
    measured slower for chained decode steps and the state is small."""
    return argnums if backend() == "tpu" else ()


def compiler_params(*dimension_semantics: str):
    """The Pallas TPU compiler parameters every pallas_call passes."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


_CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: the directory named by
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, else the fixed
    ``<checkout>/.jax_cache``.  The path is part of each cache key, so
    it never depends on a temporary name, a process id or the time."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_CHECKOUT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache at ``compile_cache_dir()``
    and return the directory.  Entry points (serve, train, benchmarks,
    chip_smoke) call this before their first compile; library imports
    and tests never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it, and no other directory is set here."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every compile: a cold serving run is dominated by many
    # sub-second compiles (the eager calibration pass) as well as the
    # two step programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
