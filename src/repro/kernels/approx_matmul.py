"""Pallas TPU kernels for approximate-multiplier matmuls.

Four kernels:

  * ``fused_qdot``     — the fused serving path: float activations in,
    float32 out.  One kernel body does (1) static-scale activation
    quantization (scales/zero-points ride as SMEM scalar operands, from
    repro.calib.static), (2) the two-stage exact-int32-dot + int16 delta
    gather, with the delta table a **kernel operand** (not a Python
    closure) so per-layer plan tables sliced out of a jax.lax.scan ride
    the same jitted body, and (3) a dequant epilogue folding the scale
    product, zero-point cross terms (asym_u8), and the mean-field
    compensation tables into the output tile before it leaves VMEM.

  * ``delta_matmul``   — the two-stage integer fast path (bit-exact,
    default ``pallas`` backend).  Mirrors the paper's two-stage
    reduction at the kernel level: stage 1 computes the *exact* int32
    tile product with ``jax.lax.dot`` (MXU), stage 2 gathers a compact
    int16 delta table ``D[a,b] = approx(a,b) - a*b``
    (core.lut.build_delta_lut, 128 KiB — half the VMEM footprint of the
    int32 product LUT) and accumulates it on the VPU.  The gather
    iterates K-subtiles of ``k_sub`` so the live index surface is
    (TM, k_sub, TN) instead of the whole (TM, TK, TN) tile; the signed
    +128 offset folds into the gather index so int8 operands need no
    pre-shift pass.  Operands are padded to block multiples internally
    (K-padding is corrected by subtracting the padded rows' constant
    ``D[off,off]`` contribution).

  * ``lut_matmul``   — paper-faithful legacy path (``pallas_legacy``):
    every scalar product goes through the 256x256 approximate-product
    LUT (256 KiB int32 pinned in VMEM), gathered per k-slice on the VPU
    while the MXU idles.  Kept for A/B benchmarking against
    ``delta_matmul`` (benchmarks/run.py kernel_microbench).

  * ``residual_matmul`` — beyond-paper approximate emulation: exact
    matmul on the MXU plus a rank-r correction  sum_r F_r(A) @ G_r(B)
    from the SVD factorization of the error surface
    (core.lut.error_factors).  Trades bit-exactness for pure-MXU FLOPs
    (the error surface's exact rank is 247).

Block shapes default to MXU-aligned (128, 128) tiles; the M/N grid axes
are marked ``parallel`` (K stays ``arbitrary`` — the output tile is
revisited as accumulator).  None of these four kernels builds for the
TPU today (kernels.platform says why, and raises if one is requested
there); on the CPU they run in interpret mode, where the tests check
them against the blocked-XLA twins in kernels/ref.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import platform


def _sub_divisor(total: int, want: int) -> int:
    """Largest divisor of ``total`` that is <= ``want`` (K-subtile size)."""
    want = max(1, min(want, total))
    while total % want:
        want -= 1
    return want


def _pad_to(x: jax.Array, m: int, n: int) -> jax.Array:
    """Zero-pad a 2-D array up to (m, n)."""
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _ceil_mul(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Kernel A: two-stage delta kernel (exact MXU product + int16 delta gather)
# ---------------------------------------------------------------------------

def _delta_gather(acc, ia, ib, dlut_flat, k_sub: int):
    """Accumulate sum_k D[ia[m,k], ib[k,n]] onto ``acc`` (TM, TN) int32,
    iterating K-subtiles of ``k_sub`` so the live index surface is
    (TM, k_sub, TN) — not the whole (TM, TK, TN) tile.  ``ia``/``ib``
    are already offset-shifted and masked in-bounds, so the per-element
    gather skips bounds clamping."""
    def body(s, acc):
        a_s = jax.lax.dynamic_slice_in_dim(ia, s * k_sub, k_sub, axis=1)
        b_s = jax.lax.dynamic_slice_in_dim(ib, s * k_sub, k_sub, axis=0)
        idx = a_s[:, :, None] * 256 + b_s[None, :, :]
        delta = dlut_flat.at[idx].get(mode="promise_in_bounds")
        return acc + delta.sum(axis=1, dtype=jnp.int32)
    return jax.lax.fori_loop(0, ia.shape[1] // k_sub, body, acc)


def _delta_matmul_kernel(a_ref, b_ref, dlut_ref, out_ref, *, offset: int,
                         k_sub: int):
    """Grid (M/TM, N/TN, K/TK); K innermost so the out tile accumulates."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.int32)          # (TM, TK)
    b = b_ref[...].astype(jnp.int32)          # (TK, TN)

    # stage 1: exact tile product, int32 accumulate (MXU on hardware)
    exact = jax.lax.dot(a, b, preferred_element_type=jnp.int32)

    # stage 2: K-subtiled delta gather (VPU).  The signed offset folds
    # into the index — no operand pre-shift pass.
    dlut = dlut_ref[...].reshape(-1)          # (65536,) int16 in VMEM
    ia = (a + offset) & 0xFF
    ib = (b + offset) & 0xFF
    out_ref[...] += _delta_gather(exact, ia, ib, dlut, k_sub)


@functools.partial(jax.jit,
                   static_argnames=("block", "offset", "k_sub"))
def delta_matmul(a: jax.Array, b: jax.Array, dlut: jax.Array,
                 block: Tuple[int, int, int] = (128, 128, 128),
                 offset: int = 0, k_sub: int = 32) -> jax.Array:
    """S[m,n] = sum_k ( a[m,k]*b[k,n] + D[a[m,k]+off, b[k,n]+off] ).

    Bit-exact approximate matmul via the two-stage decomposition.
    a: (M,K), b: (K,N) integer arrays; dlut: (256,256) int16 (or int32
    for overflow designs) delta table from core.lut.build_delta_lut.
    ``offset=128`` selects signed (int8-valued) operands against a
    signed delta table.  Shapes need NOT be block multiples: operands
    are zero-padded here and the K-padding's constant D[off,off]
    contribution is subtracted from the result.  ``k_sub`` bounds the
    stage-2 gather's index surface to (TM, k_sub, TN) per step
    (rounded down to a divisor of TK; autotuned by perf_hillclimb).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    TM, TN, TK = block
    k_sub = _sub_divisor(TK, k_sub)
    Mp, Kp, Np = _ceil_mul(M, TM), _ceil_mul(K, TK), _ceil_mul(N, TN)
    a = _pad_to(a.astype(jnp.int32), Mp, Kp)
    b = _pad_to(b.astype(jnp.int32), Kp, Np)
    grid = (Mp // TM, Np // TN, Kp // TK)
    out = pl.pallas_call(
        functools.partial(_delta_matmul_kernel, offset=offset, k_sub=k_sub),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TM, TK), lambda i, j, k: (i, k)),
            pl.BlockSpec((TK, TN), lambda i, j, k: (k, j)),
            pl.BlockSpec((256, 256), lambda i, j, k: (0, 0)),  # VMEM-pinned
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        compiler_params=platform.compiler_params(
            "parallel", "parallel", "arbitrary"),
        interpret=platform.pallas_interpret("delta_matmul"),
    )(a, b, dlut)
    if Kp > K:
        # padded k rows are (0,0) operand pairs: exact part adds 0, the
        # gather adds D[off,off] per padded row — subtract it.
        out = out - (Kp - K) * dlut[offset, offset].astype(jnp.int32)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Kernel A': fused quantize -> delta -> dequant serving kernel
# ---------------------------------------------------------------------------

def _fused_qdot_kernel(idx_ref, scal_ref, x_ref, qw_ref, dlut_ref, ntab_ref,
                       compr_ref, out_ref, acc_ref, rs_ref, rc_ref, *,
                       offset: int, lo: float, hi: float, asym: bool,
                       compensate: bool, k_sub: int, K: int):
    """Grid (M/TM, N/TN, K/TK), K innermost.

    Scalar-prefetch operands (pltpu.PrefetchScalarGridSpec):
      idx_ref   (1,) int32 — which table of the delta bank this call
                uses; consumed by dlut's BlockSpec index_map, so only
                the selected 256x256 table is DMA'd into VMEM.
      scal_ref  (8,) f32 SMEM: [sx, zx, comp_mu, kcorr_delta,
                kcorr_comp, pad...] — the calibrated static activation
                quantizer plus K-padding corrections (see fused_qdot).
    Tensor operands:
      x_ref     (TM, TK) float activations (quantized IN-kernel).
      qw_ref    (TK, TN) int32 prequantized weights.
      dlut_ref  (1, 256, 256) int16/int32 — the idx_ref-selected slice
                of the delta-table BANK: per-layer plan tables are
                kernel operands, not Python closures, so scan-sliced
                layer indices ride this same jitted body.
      ntab_ref  (4, TN) f32 per-output-column epilogue table:
                rows = [sw, zw, colsum(qw), comp_col].
      compr_ref (1, 256) f32 row compensation table mu_r.
    Scratch: int32 accumulator tile, int32 lane-replicated rowsum,
    f32 lane-replicated row-compensation sum.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rs_ref[...] = jnp.zeros_like(rs_ref)
        rc_ref[...] = jnp.zeros_like(rc_ref)

    sx = scal_ref[0]
    zx = scal_ref[1]

    # (1) static-scale activation quantization — same op sequence as the
    # unfused _quantize_act_static, so quantized values are identical.
    x = x_ref[...]                                      # (TM, TK) f32
    qx = jnp.clip(jnp.round(x / sx) + zx, lo, hi).astype(jnp.int32)
    qw = qw_ref[...].astype(jnp.int32)                  # (TK, TN)

    # (2) two-stage integer product: exact MXU dot + K-subtiled delta
    # gather against the operand table (bit-exact vs the gate level).
    acc = acc_ref[...] + jax.lax.dot(qx, qw,
                                     preferred_element_type=jnp.int32)
    dlut = dlut_ref[...].reshape(-1)
    ia = (qx + offset) & 0xFF
    ib = (qw + offset) & 0xFF
    acc_ref[...] = _delta_gather(acc, ia, ib, dlut, k_sub)

    if asym:
        # zero-point cross term needs rowsum(qx); int accumulation is
        # order-free so lane-replicated partial sums stay exact.
        rs_ref[...] = rs_ref[...] + qx.sum(axis=1, keepdims=True)
    if compensate:
        mu_r = compr_ref[...].reshape(-1)
        g = mu_r.at[ia].get(mode="promise_in_bounds")
        rc_ref[...] = rc_ref[...] + g.sum(axis=1, keepdims=True)

    # (3) dequant epilogue — runs once, on the tile still in VMEM.
    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        accf = acc_ref[...].astype(jnp.float32) - scal_ref[3]
        sw = ntab_ref[0, :][None, :]
        if compensate:
            rowc = rc_ref[...] - scal_ref[4]
            accf = accf - (rowc + ntab_ref[3, :][None, :]
                           - K * scal_ref[2])
        if asym:
            zw = ntab_ref[1, :][None, :]
            colsum = ntab_ref[2, :][None, :]
            rs = rs_ref[...].astype(jnp.float32)
            accf = accf - zw * rs - zx * colsum + K * zx * zw
        out_ref[...] = accf * (sx * sw)


@functools.partial(jax.jit, static_argnames=("asym", "compensate", "block",
                                             "offset", "k_sub"))
def fused_qdot(x: jax.Array, qw: jax.Array, dlut: jax.Array,
               scal: jax.Array, ntab: jax.Array, comp_r: jax.Array,
               dlut_idx: Optional[jax.Array] = None,
               block: Tuple[int, int, int] = (128, 128, 128),
               offset: int = 0, asym: bool = True, compensate: bool = False,
               k_sub: int = 32) -> jax.Array:
    """Fused quantized-linear: float x (M, K) -> float32 y (M, N).

    One pallas_call quantizes the activations with the calibrated STATIC
    (scale, zp) carried in ``scal``, runs the two-stage exact-dot +
    delta-gather against ``dlut``, and dequantizes in a VMEM epilogue
    folding scale product, zero-point cross terms and compensation
    tables.  ``dlut`` is a (256, 256) table or a STACKED (L, 256, 256)
    bank with ``dlut_idx`` a scalar int32 layer index: the index rides
    scalar-prefetch and the table's BlockSpec index_map selects which
    256x256 table to DMA — per-layer plan tables are kernel operands,
    and only the selected 128 KiB slice ever reaches VMEM.  Use
    kernels.ops.fused_qdot for the normalized entry point (operand
    packing + platform-adaptive lowering).

    scal: (8,) f32 [sx, zx, comp_mu, 0, 0, pad...] — positions 3/4 are
    OVERWRITTEN here with the K-padding corrections
    (Kp-K)·D[qx0+off, off] and (Kp-K)·mu_r[qx0+off] where qx0 = 0 is
    arranged by padding x with -zx·sx (which quantizes to exactly 0).
    ntab: (4, N) f32 rows [sw, zw, colsum, comp_col].
    """
    M, K = x.shape
    K2, N = qw.shape
    assert K == K2, (x.shape, qw.shape)
    if dlut.ndim == 2:
        dlut = dlut[None]
    if dlut_idx is None:
        dlut_idx = jnp.int32(0)
    idx = dlut_idx.astype(jnp.int32).reshape((1,))
    TM, TN, TK = block
    k_sub = _sub_divisor(TK, k_sub)
    Mp, Kp, Np = _ceil_mul(M, TM), _ceil_mul(K, TK), _ceil_mul(N, TN)
    lo, hi = (0.0, 255.0) if asym else (-128.0, 127.0)

    sx, zx = scal[0], scal[1]
    x0 = -zx * sx          # quantizes to exactly 0 (zx is integer-valued)
    xp = jnp.full((Mp, Kp), x0, jnp.float32)
    xp = jax.lax.dynamic_update_slice(xp, x.astype(jnp.float32), (0, 0))
    qwp = _pad_to(qw.astype(jnp.int32), Kp, Np)
    ntabp = _pad_to(ntab.astype(jnp.float32), 4, Np)
    # K-padding corrections: padded (qx, qw) pairs are (0, 0), so the
    # gathers add (Kp-K) copies of D[off, off] / mu_r[off].
    kpad = jnp.float32(Kp - K)
    scal = scal.astype(jnp.float32)
    scal = scal.at[3].set(
        kpad * dlut[idx[0], offset, offset].astype(jnp.float32))
    scal = scal.at[4].set(kpad * comp_r.reshape(-1)[offset])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # idx (int32), scal (f32) refs
        grid=(Mp // TM, Np // TN, Kp // TK),
        in_specs=[
            pl.BlockSpec((TM, TK), lambda i, j, k, ir, sr: (i, k)),   # x
            pl.BlockSpec((TK, TN), lambda i, j, k, ir, sr: (k, j)),   # qw
            pl.BlockSpec((1, 256, 256),
                         lambda i, j, k, ir, sr: (ir[0], 0, 0)),      # dlut
            pl.BlockSpec((4, TN), lambda i, j, k, ir, sr: (0, j)),    # ntab
            pl.BlockSpec((1, 256), lambda i, j, k, ir, sr: (0, 0)),   # mu_r
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k, ir, sr: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((TM, TN), jnp.int32),    # integer accumulator
            pltpu.VMEM((TM, 1), jnp.int32),     # rowsum(qx)
            pltpu.VMEM((TM, 1), jnp.float32),   # rowsum(mu_r[qx])
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fused_qdot_kernel, offset=offset, lo=lo, hi=hi,
                          asym=asym, compensate=compensate, k_sub=k_sub,
                          K=K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        compiler_params=platform.compiler_params(
            "parallel", "parallel", "arbitrary"),
        interpret=platform.pallas_interpret("fused_qdot"),
    )(idx, scal, xp, qwp, dlut, ntabp, comp_r.reshape(1, 256))
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Kernel B: LUT-gather matmul (paper-faithful legacy path)
# ---------------------------------------------------------------------------

def _lut_matmul_kernel(a_ref, b_ref, lut_ref, out_ref):
    """Grid (M/TM, N/TN, K/TK); K innermost so out tile accumulates."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.int32)          # (TM, TK)
    b = b_ref[...].astype(jnp.int32)          # (TK, TN)
    lut = lut_ref[...].reshape(-1)            # (65536,) int32 in VMEM

    def body(kk, acc):
        idx = a[:, kk][:, None] * 256 + b[kk, :][None, :]   # (TM, TN)
        return acc + jnp.take(lut, idx, axis=0)

    out_ref[...] = jax.lax.fori_loop(0, a.shape[1], body, out_ref[...])


@functools.partial(jax.jit, static_argnames=("block",))
def lut_matmul(a: jax.Array, b: jax.Array, lut: jax.Array,
               block: Tuple[int, int, int] = (128, 128, 128)) -> jax.Array:
    """S[m,n] = sum_k LUT[a[m,k], b[k,n]]   (uint8-valued operands).

    a: (M,K), b: (K,N) integer arrays in [0,255]; lut: (256,256) int32.
    M,K,N must be multiples of the block shape (pad upstream; the delta
    kernel pads internally and is the default backend).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    TM, TN, TK = block
    assert M % TM == 0 and N % TN == 0 and K % TK == 0, \
        (a.shape, b.shape, block)
    grid = (M // TM, N // TN, K // TK)
    return pl.pallas_call(
        _lut_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TM, TK), lambda i, j, k: (i, k)),
            pl.BlockSpec((TK, TN), lambda i, j, k: (k, j)),
            pl.BlockSpec((256, 256), lambda i, j, k: (0, 0)),  # VMEM-pinned
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        compiler_params=platform.compiler_params(
            "parallel", "parallel", "arbitrary"),
        interpret=platform.pallas_interpret("lut_matmul"),
    )(a.astype(jnp.int32), b.astype(jnp.int32), lut.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Kernel C: exact MXU matmul + rank-r error correction (beyond-paper)
# ---------------------------------------------------------------------------

def _residual_kernel(a_ref, b_ref, f_ref, g_ref, out_ref, *, offset: int = 0):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.int32)            # (TM, TK)
    b = b_ref[...].astype(jnp.int32)            # (TK, TN)
    F = f_ref[...]                              # (256, r) f32
    G = g_ref[...]                              # (r, 256) f32

    # exact product on the MXU
    exact = jax.lax.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    # rank-r correction, also MXU: (TM, TK*r) @ (TK*r, TN).  The gathers
    # index the (offset-shifted) operand value; `offset=128` selects the
    # signed factor tables (core.lut.signed_error_factors).
    r = F.shape[1]
    tm, tk = a.shape
    tn = b.shape[1]
    Fa = jnp.take(F, (a + offset).reshape(-1), axis=0).reshape(tm, tk * r)
    Gb = jnp.take(G, (b + offset).reshape(-1), axis=1)     # (r, TK*TN)
    Gb = Gb.reshape(r, tk, tn).transpose(1, 0, 2).reshape(tk * r, tn)
    corr = jax.lax.dot(Fa, Gb, precision=jax.lax.Precision.HIGHEST)
    out_ref[...] += exact + corr


@functools.partial(jax.jit, static_argnames=("block", "offset"))
def residual_matmul(a: jax.Array, b: jax.Array, F: jax.Array, G: jax.Array,
                    block: Tuple[int, int, int] = (128, 128, 128),
                    offset: int = 0) -> jax.Array:
    """Exact matmul + rank-r approximate-error correction (float32 out).

    ``offset`` shifts the factor-table gathers (128 for int8 operands
    against signed factor tables); the exact MXU matmul always runs on
    the raw operand values.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    TM, TN, TK = block
    assert M % TM == 0 and N % TN == 0 and K % TK == 0
    r = F.shape[1]
    grid = (M // TM, N // TN, K // TK)
    return pl.pallas_call(
        functools.partial(_residual_kernel, offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TM, TK), lambda i, j, k: (i, k)),
            pl.BlockSpec((TK, TN), lambda i, j, k: (k, j)),
            pl.BlockSpec((256, r), lambda i, j, k: (0, 0)),
            pl.BlockSpec((r, 256), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=platform.compiler_params(
            "parallel", "parallel", "arbitrary"),
        interpret=platform.pallas_interpret("residual_matmul"),
    )(a.astype(jnp.int32), b.astype(jnp.int32),
      F.astype(jnp.float32), G.astype(jnp.float32))
