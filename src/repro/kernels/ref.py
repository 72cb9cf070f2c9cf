"""Pure-jnp oracles for the approximate-multiply kernels.

These are the semantic ground truth the Pallas kernels are validated
against (tests sweep shapes/dtypes and assert_allclose).  Operands are
uint8-valued ([0, 255], offset=0, the paper's unsigned semantics) or
int8-valued ([-128, 127], offset=128) — ``offset`` shifts the LUT index
so signed tables built by core.lut.build_signed_lut resolve directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def approx_mul_ref(a, b, lut: np.ndarray, offset: int = 0):
    """Elementwise approximate product via the 256x256 LUT.

    a, b: integer arrays (broadcastable); index = value + offset must
    land in [0, 255]. Returns int32.
    """
    lut = jnp.asarray(lut, dtype=jnp.int32)
    flat = lut.reshape(-1)
    idx = (a.astype(jnp.int32) + offset) * 256 + (b.astype(jnp.int32) + offset)
    return jnp.take(flat, idx, axis=0)


def approx_matmul_ref(a, b, lut: np.ndarray, offset: int = 0):
    """S[m,n] = sum_k LUT[a[m,k]+offset, b[k,n]+offset]  (int32 acc).

    a: (M,K), b: (K,N); uint8-valued with offset=0, int8-valued with
    offset=128 and a signed LUT.
    """
    lut = jnp.asarray(lut, dtype=jnp.int32)
    flat = lut.reshape(-1)
    idx = ((a.astype(jnp.int32) + offset)[:, :, None] * 256
           + (b.astype(jnp.int32) + offset)[None, :, :])
    return jnp.take(flat, idx, axis=0).sum(axis=1)


def _pick_k_block(K: int, k_block: int) -> int:
    """Largest candidate K-block (<= k_block, from the fixed ladder)
    that divides K — shared by the blocked delta twins."""
    for kb in (k_block, 64, 32, 16, 8, 4, 2, 1):
        if kb <= k_block and K % kb == 0:
            return kb
    return 1


def delta_matmul_ref(a, b, dlut: np.ndarray, offset: int = 0,
                     k_block: int = 32, layer=None):
    """Two-stage fast path, XLA lowering: exact dot + blocked delta
    gather (int32 out).

    S[m,n] = sum_k ( a[m,k]*b[k,n] + D[a[m,k]+off, b[k,n]+off] ) — the
    XLA twin of kernels.approx_matmul.delta_matmul and what the 'delta'
    backend lowers with on every platform (kernels.platform says why
    the TPU runs it too): the bulk of the arithmetic is a plain
    dot (MXU/BLAS-friendly) and the gathered payload is the half-width
    int16 delta table (core.lut.build_delta_lut).  Unlike the old
    approx_matmul_ref it never materializes the whole (M,K,N) index
    surface: a lax.scan over K-blocks of ``k_block`` keeps the gather
    working set cache-sized, and the index is masked to [0, 65535] so
    the lookup can skip per-element bounds clamping.  The gather reads
    an int32 widening of the delta table: host/GPU gathers are natively
    32-bit (an int16 payload costs an extra convert — measured slower),
    while the int16 packing is what matters for TPU VMEM, i.e. for the
    Pallas kernel.  ~2x faster than the legacy product-LUT Pallas
    kernel at 256^3 on the CPU container (BENCH_kernels.json).

    ``layer``: with a stacked table BANK dlut (L, 256, 256) (the
    mixed-design plan path — quant.linear.register_dlut_bank), a scalar
    int32 index selecting the layer's table.  The selection folds into
    the gather base (layer*65536): no 256 KiB table slice materializes
    per call, which is what makes per-layer plan tables scan-friendly.
    """
    M, K = a.shape
    N = b.shape[1]
    exact = exact_matmul_ref(a, b)
    flat = jnp.asarray(dlut, dtype=jnp.int32).reshape(-1)
    kb = _pick_k_block(K, k_block)
    ab = (a.astype(jnp.int32) + offset).reshape(M, K // kb, kb)
    ab = (ab & 0xFF).transpose(1, 0, 2) * 256               # (nb, M, kb)
    if layer is not None:
        ab = ab + layer.astype(jnp.int32) * 65536
    bb = ((b.astype(jnp.int32) + offset) & 0xFF).reshape(K // kb, kb, N)

    def body(acc, inp):
        ak, bk = inp
        idx = ak[:, :, None] + bk[None, :, :]               # (M, kb, N)
        g = flat.at[idx].get(mode="promise_in_bounds")
        return acc + g.sum(axis=1), None

    out, _ = jax.lax.scan(body, exact, (ab, bb))
    return out


def exact_matmul_ref(a, b):
    """Exact integer matmul oracle (int32)."""
    return jnp.matmul(a.astype(jnp.int32), b.astype(jnp.int32),
                      preferred_element_type=jnp.int32)


def fused_qdot_ref(x, qw, dlut, scal, ntab, comp_r, offset: int = 0,
                   asym: bool = True, compensate: bool = False,
                   k_block: int = 32, layer=None):
    """Blocked-XLA twin of the fused quantize -> (exact dot + delta
    gather) -> dequant serving path: the oracle of the one-hot kernel
    (kernels.approx_matmul.onehot_qdot, which repeats its quantizer and
    epilogue op for op) and the qdot's lowering off the TPU (float x in,
    float32 out, same operand layout as kernels.ops.fused_qdot).

    x: (M, K) float; qw: (K, N) int32 prequantized weights;
    dlut: (256, 256) delta table, or a stacked (L, 256, 256) bank with
    ``layer`` a scalar int32 index (the mixed-design plan path: the
    bank rides as one jit constant, the layer selection folds into the
    gather base — no per-call table slice); scal: (>=3,) f32 [sx, zx,
    comp_mu, ...]; ntab: (4, N) f32 rows [sw, zw, colsum, comp_col];
    comp_r: (256,) f32.

    Unlike the general delta_matmul_ref oracle this twin OWNS its
    operand domain — qx comes out of the in-graph clip and qw out of
    prequantize, both provably in [lo, hi] — so the gather drops the
    defensive & 0xFF masks and folds the signed +128 shifts of BOTH
    operands into one compile-time index constant (offset*257): no
    per-step shift pass over the static (K, N) weight operand at all.
    It does one scalar gather per multiply-accumulate (M*K*N of them).

    Every float epilogue op mirrors the unfused quant.linear pipeline's
    op sequence, so fused-vs-unfused differences stay at float-reduction
    ULP level (the integer product itself is bit-exact by the delta
    decomposition).  No padding needed: the K-blocked scan handles any
    shape.
    """
    sx, zx = scal[0], scal[1]
    lo, hi = (0.0, 255.0) if asym else (-128.0, 127.0)
    qx = jnp.clip(jnp.round(x.astype(jnp.float32) / sx) + zx,
                  lo, hi).astype(jnp.int32)
    M, K = qx.shape
    N = qw.shape[1]
    exact = exact_matmul_ref(qx, qw)
    flat = jnp.asarray(dlut, dtype=jnp.int32).reshape(-1)
    kb = _pick_k_block(K, k_block)
    # folded offsets: D[(a+off), (b+off)] flattens to a*256 + b + off*257,
    # with both operands' shifts — and the bank's layer base — riding
    # the (M, K)-side affine.
    ab = qx * 256 + offset * 257
    if layer is not None:
        ab = ab + layer.astype(jnp.int32) * 65536
    ab = ab.reshape(M, K // kb, kb).transpose(1, 0, 2)      # (nb, M, kb)
    bb = qw.astype(jnp.int32).reshape(K // kb, kb, N)

    def body(acc, inp):
        ak, bk = inp
        idx = ak[:, :, None] + bk[None, :, :]               # (M, kb, N)
        g = flat.at[idx].get(mode="promise_in_bounds")
        return acc + g.sum(axis=1), None

    prod, _ = jax.lax.scan(body, exact, (ab, bb))
    accf = prod.astype(jnp.float32)
    sw = ntab[0, :][None, :]
    if compensate:
        rowc = jnp.take(comp_r, qx + offset,
                        axis=0).sum(-1, keepdims=True)
        accf = accf - (rowc + ntab[3, :][None, :] - K * scal[2])
    if asym:
        zw = ntab[1, :][None, :]
        colsum = ntab[2, :][None, :]
        rowsum = qx.sum(axis=-1, keepdims=True).astype(jnp.float32)
        accf = accf - zw * rowsum - zx * colsum + K * zx * zw
    return accf * (sx * sw)


def _rmsnorm(x, gamma, eps: float = 1e-6):
    """Mirror of models.layers.rmsnorm (kept local: ref.py stays pure
    jnp with no model-layer imports — models imports kernels)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)) * gamma


def _rope(x, positions, theta: float):
    """Mirror of models.layers.rope. x: (B, S, H, D)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.asarray(positions, jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[:, :, None, None] * freqs[None, None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def decode_rows(q, k, idx, *, rope_theta: float = 10000.0, q_gain=None,
                k_gain=None):
    """The decode step's per-row prologue: (optional) qk rmsnorm, then
    rope at the slot's cache position.  q: (B, S, n_heads, hd); k:
    (B, S, n_kv, hd); idx: scalar int32 or (B,) per-slot positions.
    Shared by the twin and the Pallas path of kernels.ops, so both
    append the same cache rows bit for bit."""
    S = q.shape[1]
    positions = (idx[:, None] + jnp.arange(S)) if idx.ndim == 1 \
        else (idx + jnp.arange(S))
    if q_gain is not None:
        q = _rmsnorm(q, q_gain)
        k = _rmsnorm(k, k_gain)
    if rope_theta:
        q = _rope(q, positions, rope_theta)
        k = _rope(k, positions, rope_theta)
    return q, k


def append_rows(k_cache, v_cache, k, v, idx):
    """Write the new rows into the caches at ``idx`` (scalar, or (B,)
    per slot), cast to the cache dtype exactly like the cache update of
    the generic attention path."""
    if idx.ndim == 1:
        upd = jax.vmap(
            lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i, 0, 0)))
        return (upd(k_cache, k.astype(k_cache.dtype), idx),
                upd(v_cache, v.astype(v_cache.dtype), idx))
    return (jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                         (0, idx, 0, 0)),
            jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                         (0, idx, 0, 0)))


def decode_attention_ref(q, k, v, k_cache, v_cache, idx, *, n_heads: int,
                         n_kv: int, head_dim: int,
                         rope_theta: float = 10000.0, window=None,
                         q_gain=None, k_gain=None):
    """XLA twin of the fused decode-step attention/cache op
    (kernels.ops.decode_attention), the lowering 'auto' picks off the
    TPU.

    One logical op covers what the decode step previously spread over
    models.layers.attention: (optional) qk rmsnorm, rope at the slot's
    cache position, the KV-cache append, and masked single-query GQA
    attention over the cache.  The op sequence REPLICATES the generic
    attention path bit for bit (same einsum contractions, same -1e30
    mask + f32 softmax, new k/v read back through the cache dtype), so
    routing the serve step through it changes nothing numerically —
    asserted by tests/test_decode_attention.py.

    q: (B, 1, n_heads, hd) pre-norm pre-rope query projection;
    k, v: (B, 1, n_kv, hd) fresh key/value projections.
    k_cache/v_cache: (B, S_max, n_kv, hd) (any float dtype; new rows are
    cast on append exactly like the cache update they replace).
    idx: scalar int32 — the uniform cache position — or (B,) int32
    per-slot positions (batched MULTI-SLOT decode: each request sits at
    its own depth, what the continuous-batching driver schedules).
    window: optional sliding-window size.  q_gain/k_gain: qk-norm gains.

    Returns (out (B, 1, n_heads*hd) f32, k_cache', v_cache').
    """
    import math
    B, S = q.shape[:2]
    per_slot = idx.ndim == 1
    positions = (idx[:, None] + jnp.arange(S)) if per_slot \
        else (idx + jnp.arange(S))
    q, k = decode_rows(q, k, idx, rope_theta=rope_theta, q_gain=q_gain,
                       k_gain=k_gain)
    ck, cv = append_rows(k_cache, v_cache, k, v, idx)
    S_k = ck.shape[1]
    group = n_heads // max(n_kv, 1)
    qg = q.reshape(B, S, n_kv, group, head_dim)
    lg = jnp.einsum("bsngd,btnd->bngst", qg, ck) / math.sqrt(head_dim)
    kpos = jnp.arange(S_k)
    kv_limit = idx + S
    if per_slot:
        m = (kpos[None, None, :] <= positions[:, :, None]) \
            & (kpos[None, None, :] < kv_limit[:, None, None])
        if window is not None:
            m = m & (kpos[None, None, :] > positions[:, :, None] - window)
        mb = m[:, None, None]                       # (B, 1, 1, S, S_k)
    else:
        m = (kpos[None, :] <= positions[:, None]) \
            & (kpos[None, :] < kv_limit)
        if window is not None:
            m = m & (kpos[None, :] > positions[:, None] - window)
        mb = m[None, None, None]
    lg = jnp.where(mb, lg, -1e30)
    pr = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bngst,btnd->bsngd", pr, cv)
    return out.reshape(B, S, n_heads * head_dim), ck, cv


def residual_corrected_matmul_ref(a, b, F: np.ndarray, G: np.ndarray,
                                  offset: int = 0):
    """Beyond-paper fast path oracle: exact matmul + rank-r error model.

    approx(a,b) ~= a*b + sum_r F[a+offset,r] * G[r,b+offset]; contraction
    distributes:
       S = A@B + sum_r F_r(A) @ G_r(B)
    F: (256, r) float32, G: (r, 256) float32 (core.lut.error_factors, or
    signed_error_factors with offset=128 for int8 operands).
    """
    exact = exact_matmul_ref(a, b).astype(jnp.float32)
    Fa = jnp.take(jnp.asarray(F), a.astype(jnp.int32) + offset, axis=0)
    Gb = jnp.take(jnp.asarray(G), b.astype(jnp.int32) + offset, axis=1)
    corr = jnp.einsum("mkr,rkn->mn", Fa, Gb)
    return exact + corr
