"""Pallas TPU kernel for the attention half of the fused decode step.

``decode_attention_step`` is masked single-query GQA attention over a
slot's KV cache, one grid step per (batch slot b, cache tile s):

  * each slot attends up to its own cache position ``pos[b]``
    (scalar-prefetch operand — per-slot positions are what the batched
    MULTI-SLOT decode of the continuous-batching driver schedules);
  * the cache is read tile by tile with an online-softmax accumulator
    (flash-decode style: running max / denominator / weighted-value
    scratch), so S_max never has to fit VMEM whole — ``block_s`` tiles
    it;
  * every contraction is a 2-D dot per KV head: the cache is viewed as
    (B, S_max, n_kv·hd), so KV head g is the lane-aligned column block
    [g·hd, (g+1)·hd) of a tile, and each query head keeps the logits and
    values of its own KV head.  Mosaic lowers these dots; it refuses the
    batched (head-major) ``dot_general`` an earlier version used.

The rest of the decode step — qk-norm, rope at the cache position and
the KV-cache append — runs as XLA ops shared with the twin
(``ref.decode_rows`` / ``ref.append_rows``), so the appended cache rows
are the twin's bit for bit, and the kernel reads the cache after the
append.  ``kernels.ops.decode_attention`` composes the step; its twin
``ref.decode_attention_ref`` is bit-matched to the generic attention
path.  The kernel agrees with the twin to f32-softmax-reassociation
ULPs (online vs two-pass softmax), asserted in
tests/test_decode_attention.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform


def _cache_tile(s_max: int, block_s: int, itemsize: int) -> int:
    """Cache rows per grid step: the whole cache when it fits in
    ``block_s`` rows (a block equal to the array dimension is always
    legal), else ``block_s`` rounded down to the dtype's sublane tile (8
    rows for 4-byte, 16 for 2-byte elements).  The grid then takes
    ceil(s_max / ts) steps, and the kernel masks the rows of a ragged
    last tile, so any cache length works without a cache-sized block."""
    if s_max <= block_s:
        return s_max
    sub = max(32 // (8 * itemsize), 1) * 8
    return max(block_s // sub, 1) * sub


def _decode_attn_kernel(pos_ref, q_ref, kc_ref, vc_ref, o_ref,
                        acc_ref, mx_ref, den_ref, *, group: int,
                        window: Optional[int], ts: int, s_max: int):
    """Grid (B, ceil(S_max/TS)); s innermost so the online-softmax
    scratch accumulates across the cache tiles of one slot.  Rows of a
    ragged last tile past S_max hold no cache data: their logits are
    masked (they lie past every position) and their values zeroed."""
    b = pl.program_id(0)
    s = pl.program_id(1)
    p = pos_ref[b]
    H, hd = acc_ref.shape

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mx_ref[...] = jnp.full_like(mx_ref, -1e30)
        den_ref[...] = jnp.zeros_like(den_ref)

    q = q_ref[0]                                    # (H, hd) f32
    kt = kc_ref[0]                                  # (TS, Kv·hd)
    vt = vc_ref[0].astype(jnp.float32)
    if s_max % ts:                                  # ragged last tile
        vrow = s * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
        vt = jnp.where(vrow < s_max, vt, 0.0)
    head_kv = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // group
    nt = (((1,), (1,)), ((), ()))                   # q @ k^T
    hi = jax.lax.Precision.HIGHEST

    lg = jnp.zeros((H, ts), jnp.float32)
    for g in range(H // group):
        k_g = kt[:, g * hd:(g + 1) * hd].astype(jnp.float32)    # (TS, hd)
        lg_g = jax.lax.dot_general(q, k_g, nt, precision=hi,
                                   preferred_element_type=jnp.float32)
        lg = jnp.where(head_kv == g, lg_g, lg)
    lg = lg / math.sqrt(hd)

    trow = s * ts + jax.lax.broadcasted_iota(jnp.int32, (1, ts), 1)
    valid = trow <= p
    if window is not None:
        valid = valid & (trow > p - window)
    lg = jnp.where(valid, lg, -1e30)

    m_new = jnp.maximum(mx_ref[...], jnp.max(lg, axis=1, keepdims=True))
    alpha = jnp.exp(mx_ref[...] - m_new)
    pe = jnp.exp(lg - m_new)                                 # (H, TS)
    den_ref[...] = den_ref[...] * alpha + pe.sum(axis=1, keepdims=True)
    pv = jnp.zeros((H, hd), jnp.float32)
    for g in range(H // group):
        v_g = vt[:, g * hd:(g + 1) * hd]                        # (TS, hd)
        pv_g = jax.lax.dot(pe, v_g, precision=hi,
                           preferred_element_type=jnp.float32)
        pv = jnp.where(head_kv == g, pv_g, pv)
    acc_ref[...] = acc_ref[...] * alpha + pv
    mx_ref[...] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _epilogue():
        o_ref[0] = acc_ref[...] / den_ref[...]


@functools.partial(jax.jit, static_argnames=("window", "group", "block_s"))
def decode_attention_step(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, pos: jax.Array, *,
                          group: int, window: Optional[int] = None,
                          block_s: int = 128) -> jax.Array:
    """Single-query GQA attention of a batch of slots over their caches.

    q: (B, H, hd) f32, already qk-normed and roped; k_cache/v_cache:
    (B, S_max, Kv, hd) with this step's rows already appended; pos: (B,)
    int32 per-slot positions of the query (cache rows <= pos are
    attended, and > pos - window with a sliding window).  ``group`` =
    H // Kv query heads per KV head.  Returns (B, H, hd) f32.
    """
    B, H, hd = q.shape
    S_max, Kv = k_cache.shape[1], k_cache.shape[2]
    ts = _cache_tile(S_max, block_s, k_cache.dtype.itemsize)
    kc = k_cache.reshape(B, S_max, Kv * hd)         # free: contiguous
    vc = v_cache.reshape(B, S_max, Kv * hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                      # pos
        grid=(B, pl.cdiv(S_max, ts)),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, s, pr: (b, 0, 0)),
            pl.BlockSpec((1, ts, Kv * hd), lambda b, s, pr: (b, s, 0)),
            pl.BlockSpec((1, ts, Kv * hd), lambda b, s, pr: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, s, pr: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, hd), jnp.float32),       # online-softmax acc
            pltpu.VMEM((H, 1), jnp.float32),        # running max
            pltpu.VMEM((H, 1), jnp.float32),        # running denominator
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, group=group, window=window,
                          ts=ts, s_max=S_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
        compiler_params=platform.compiler_params("parallel", "arbitrary"),
        interpret=platform.pallas_interpret("decode_attention_step"),
    )(pos.astype(jnp.int32), q.astype(jnp.float32), kc, vc)
