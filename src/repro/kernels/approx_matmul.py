"""Pallas TPU kernels for approximate-multiplier matmuls.

Four kernels:

  * ``onehot_qdot``    — the fused serving qdot, what the TPU runs
    (kernels.ops.fused_qdot): float activations in, float32 out, one
    pallas_call.  It quantizes the activations with the static scale,
    selects their rows of the approximate product table P = a*b + D (D
    the delta table, or the selected table of a bank) with a one-hot
    contraction on the MXU against P's two bfloat16 byte planes, joins
    the planes into int32 rows, looks each weight up in its row with the
    TPU's lane gather (two 128-lane halves, one select), and dequantizes
    the tile before it leaves VMEM.  Bit-exact, and the same cost for
    every table.

  * ``delta_matmul``   — the two-stage integer path (bit-exact, the
    ``pallas`` backend).  Mirrors the paper's two-stage reduction at the
    kernel level: stage 1 computes the *exact* int32 tile product with
    ``jax.lax.dot`` (MXU), stage 2 gathers a compact int16 delta table
    ``D[a,b] = approx(a,b) - a*b`` (core.lut.build_delta_lut, 128 KiB —
    half the VMEM footprint of the int32 product LUT) and accumulates it
    on the VPU.  The gather iterates K-subtiles of ``k_sub`` so the live
    index surface is (TM, k_sub, TN) instead of the whole (TM, TK, TN)
    tile; the signed +128 offset folds into the gather index so int8
    operands need no pre-shift pass.  Operands are padded to block
    multiples internally (K-padding is corrected by subtracting the
    padded rows' constant ``D[off,off]`` contribution).

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

The M/N grid axes are marked ``parallel`` (K stays ``arbitrary`` — the
output tile is revisited as accumulator).  The last three gather per
element from whole tables and do not build for the TPU (kernels.platform
says why, and raises if one is requested there); on the CPU every kernel
runs in interpret mode, where the tests check them against
kernels/ref.py.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
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
# Kernel A': the fused qdot: rows selected on the MXU, looked up by lane gather
# ---------------------------------------------------------------------------

def product_planes(dlut, layer=None, offset: int = 0):
    """The full approximate product table of a delta table, centred and
    split into two byte planes, each exact in bfloat16.

    P[a + off, b + off] = a*b + D[a + off, b + off] (indexed as the twin
    indexes D, so both operand modes work); c is the midpoint of P's
    range, and P - c = 256*hi + lo with lo in [0, 255].  bfloat16 holds
    both planes exactly while |P - c| < 2**16: every registered unsigned
    and signed design (P spans at most 65,025), and any table whose
    products span less than 2**17.  ``layer`` selects a table of a
    stacked (L, 256, 256) bank.  A table known while tracing (not a
    tracer) is split in numpy, so the planes are constants of the
    program.  Returns (planes (256, 512) f32: row a holds the hi plane's
    row a, then the lo plane's; c)."""
    xp = jnp if isinstance(dlut, jax.core.Tracer) or layer is not None \
        else np
    if layer is not None:
        dlut = jax.lax.dynamic_index_in_dim(dlut, layer, keepdims=False)
    v = xp.arange(256, dtype=xp.int32) - offset
    p = v[:, None] * v[None, :] + xp.asarray(dlut).astype(xp.int32)
    c = (p.max() + p.min()) >> 1
    p = p - c
    return xp.concatenate([p >> 8, p & 255], axis=1).astype(xp.float32), c


_KS = 8     # weight rows per gather group: one (8, 128) int32 tile
_KO = 16    # weight rows per one-hot chunk: one (16, 128) bfloat16 tile
_MC = 4     # activation rows whose partial sums one pass keeps in vregs
_TM = 32    # most activation rows per tile (the row selection unrolls them)
_TN = 512   # most weight columns per tile
_KB = 512   # most weight rows per grid step
# Most activation rows per tile that take the lane gather; taller tiles
# take the one-hot contraction.  The gather costs 2*tm lane permutes per
# 1,024 weight elements, the one-hot about 1,000 VPU ops whatever tm is.
# TPU v5e, w_gate|up (K 2048, N 12288), Design #2, isolated calls, ms:
# M 4: gather 1.28, one-hot 2.75; M 16: 5.05, 4.47; M 32: 10.02, 6.52.
_TG = 12


def _largest_divisor(total: int, step: int, most: int) -> int:
    """The largest multiple of ``step`` up to ``most`` that divides
    ``total`` (itself a multiple of ``step``)."""
    return max(d for d in range(step, min(most, total) + 1, step)
               if total % d == 0)


def contraction(M: int, K: int, N: int) -> str:
    """The weight-side contraction ``onehot_qdot`` runs at this shape:
    'gather' (lane gathers from the selected rows) or 'onehot' (a
    one-hot of the weights on the MXU), chosen by the tile's rows."""
    return "gather" if _onehot_blocks(M, K, N)[0] <= _TG else "onehot"


def _onehot_blocks(M: int, K: int, N: int):
    """Block sizes from the shapes: M rows per tile (all of M up to 32),
    N columns per tile (up to 512), and K rows per grid step (a multiple
    of 128 up to 512), each dividing the padded extent where it can so
    that no operand is padded at the serving shapes.  Every buffer fits
    the default scoped VMEM: a block's selected rows (tm*kb*256 int32,
    or 2*tm*kb*256 bfloat16) at most 2 MiB, the accumulator over all of
    N (tm*N int32, or 2*tm*N f32) at most 4 MiB where tm can shrink to
    8."""
    Np = _ceil_mul(N, 128)
    tm = M if M <= _TM else _TM
    while tm > 8 and 2 * tm * Np * 4 > (4 << 20):
        tm = max(8, tm // 2 // 8 * 8)
    tn = _largest_divisor(Np, 128, _TN)
    most = max(128, (2 << 20) // (tm * 256 * 4) // 128 * 128)
    kb = _largest_divisor(_ceil_mul(K, 128), 128, min(_KB, most))
    return tm, tn, kb


def _onehot_kernel(*refs, scalars, cols, offset: int, K: int, asym: bool,
                   compensate: bool):
    """Grid (M tiles, K blocks, N tiles), N innermost, so that the rows a
    K block's activations select serve every N tile.

    Scalar prefetch (SMEM, one (1,) ref each, named by ``scalars``): kc
    int32 (K*c, the product table centre's share), sx, and as the mode
    needs zx and the compensation mean mu, then the per-tensor epilogue
    parameters of sw, zw (f32).
    Tensor operands:
      x_ref   (tm, kb) f32 activations, quantized here.
      qw_ref  (kb/ks, ks, tn) int32 weights, ks = 8 (gather) or 16
              (one-hot).
      p_ref   (256, 512) bf16 product planes (product_planes).
      cr_ref  (1, 256) f32 row compensation table (with compensation).
      then one (1, tn) f32 row per per-column epilogue parameter, named
      by ``cols`` (of sw, zw, colsum, comp_col).
    Output: (tm, tn) f32; its index map holds the first N tile until the
    last K block, so only finished tiles are written back.
    Scratch: acc, the sums over K: (N tiles, tm, tn) int32 of P - c
    (gather), or (N tiles, 2*tm, tn) f32 of the hi and lo planes
    (one-hot); r, the rows the block's activations select: (tm, kb, 256)
    int32 of P - c, or (2*tm, kb, 256) bf16 of both planes; the rowsum
    of qx (tm, 1) f32 (asym); the histogram of qx (tm, 256) f32 (with
    compensation).

    At each K block's first N tile the activations select their rows of
    both planes on the MXU (a one-hot of qx against the planes, exact:
    one nonzero term).  The weight side is a lookup within each row,
    out[m, n] += P[qx[m, k] + off, qw[k, n] + off] - c, done one of two
    ways (``contraction``), the same integers either way:

      gather  the planes are joined into int32 rows R[m, k, :].  Per
              (8, 128) tile of weights the kernel gathers both 128-lane
              halves of the 8 rows' R along lanes with the index
              (qw + off) & 127 (the TPU's lane permute) and keeps the
              half that (qw + off) >= 128 names; the index and the half
              serve every activation row.  Partial sums stay in vregs,
              8 sublanes each, and are reduced into the accumulator once
              per K block and N tile.
      one-hot per weight row k the one-hot onehot[u, n] = (qw[k, n] +
              off == u) is built on the VPU from an iota and a compare,
              only in VMEM, and the MXU contracts it with both planes'
              rows (exact in f32: every sum is an integer below 2**24).

    The quantizer and the epilogue are kernels.ref.fused_qdot_ref's op
    for op."""
    ns, nc = len(scalars), len(cols)
    sc = dict(zip(scalars, refs[:ns]))
    x_ref, qw_ref, p_ref = refs[ns:ns + 3]
    i = ns + 3
    cr_ref = refs[i] if compensate else None
    i += compensate
    col = dict(zip(cols, refs[i:i + nc]))
    out_ref, acc_ref, r_ref, *rest = refs[i + nc:]
    rs_ref = rest.pop(0) if asym else None
    hist_ref = rest.pop(0) if compensate else None

    def val(name):          # a per-column row or a per-tensor scalar
        return col[name][...] if name in col else sc[name][0]

    k, j = pl.program_id(1), pl.program_id(2)
    tm, kb = x_ref.shape
    ns = qw_ref.shape[-1] // 128                # 128-lane slices of a tile
    gather = tm <= _TG

    @pl.when(k == 0)
    def _init():
        acc_ref[j] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)

        @pl.when(j == 0)
        def _init_rows():
            if asym:
                rs_ref[...] = jnp.zeros_like(rs_ref)
            if compensate:
                hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(j == 0)
    def _select_rows():
        # ref.fused_qdot_ref's quantizer, op for op
        q = jnp.round(x_ref[...] / sc["sx"][0])
        if asym:
            q = q + sc["zx"][0]
        qx = jnp.clip(q, *((0.0, 255.0) if asym else (-128.0, 127.0)))
        qx = qx.astype(jnp.int32)
        qv = qx + offset                            # the table's row
        if K % kb:          # the last block reaches past K: no row there
            kk = k * kb + jax.lax.broadcasted_iota(jnp.int32, qx.shape, 1)
            qx = jnp.where(kk < K, qx, 0)
            qv = jnp.where(kk < K, qv, -1)
        if asym:
            rs_ref[...] += jnp.sum(qx.astype(jnp.float32), axis=1,
                                   keepdims=True)
        qt = jnp.transpose(qv)                      # (kb, tm)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (kb, 256), 1)
        planes = p_ref[...]
        for m in range(tm):
            oh = (qt[:, m:m + 1] == lanes).astype(jnp.bfloat16)
            rows = jax.lax.dot(oh, planes,
                               preferred_element_type=jnp.float32)
            if gather:
                r_ref[m] = (rows[:, :256].astype(jnp.int32) * 256
                            + rows[:, 256:].astype(jnp.int32))
            else:
                r_ref[m] = rows[:, :256].astype(jnp.bfloat16)
                r_ref[tm + m] = rows[:, 256:].astype(jnp.bfloat16)
            if compensate:
                hist_ref[m:m + 1, :] += jnp.sum(oh.astype(jnp.float32),
                                                axis=0, keepdims=True)

    def lookup(m0, mc):
        """Sum R[m, k, qw[k, n] + off] over the block's k for the rows
        m0 .. m0 + mc, into the accumulator."""
        def group(g, part):
            w = qw_ref[g]                                   # (8, tn)
            if offset:
                w = w + offset
            idx, upper = w & 127, w >= 128
            k8 = pl.ds(pl.multiple_of(g * _KS, _KS), _KS)
            part = list(part)
            for m in range(mc):
                lo, hi = r_ref[m0 + m, k8, :128], r_ref[m0 + m, k8, 128:]
                for s in range(ns):
                    ix = idx[:, s * 128:(s + 1) * 128]
                    got = jnp.where(
                        upper[:, s * 128:(s + 1) * 128],
                        jnp.take_along_axis(hi, ix, axis=1,
                                            mode="promise_in_bounds"),
                        jnp.take_along_axis(lo, ix, axis=1,
                                            mode="promise_in_bounds"))
                    part[m * ns + s] += got
            return tuple(part)

        zero = jnp.zeros((_KS, 128), jnp.int32)
        part = jax.lax.fori_loop(0, kb // _KS, group, (zero,) * (mc * ns))
        for m in range(mc):
            for s in range(ns):
                acc_ref[j, pl.ds(m0 + m, 1), pl.ds(s * 128, 128)] += \
                    jnp.sum(part[m * ns + s], axis=0, keepdims=True)

    if gather:
        for m0 in range(0, tm, _MC):
            lookup(m0, min(_MC, tm - m0))
    else:
        u = jax.lax.broadcasted_iota(jnp.int32, (256, ns * 128), 0) - offset

        def chunk(c, acc):
            w = qw_ref[c]                                   # (16, tn)
            rc = r_ref[:, pl.ds(pl.multiple_of(c * _KO, _KO), _KO), :]
            for jj in range(_KO):
                onehot = (w[jj:jj + 1, :] == u).astype(jnp.bfloat16)
                acc = acc + jax.lax.dot(rc[:, jj, :], onehot,
                                        preferred_element_type=jnp.float32)
            return acc

        acc_ref[j] = jax.lax.fori_loop(0, kb // _KO, chunk, acc_ref[j])

    @pl.when(k == pl.num_programs(1) - 1)
    def _epilogue():
        acc = acc_ref[j]
        if gather:
            prod = acc + sc["kc"][0]
        else:
            prod = (acc[:tm].astype(jnp.int32) * 256
                    + acc[tm:].astype(jnp.int32) + sc["kc"][0])
        accf = prod.astype(jnp.float32)
        if compensate:
            rowc = jnp.sum(hist_ref[...] * cr_ref[...], axis=1,
                           keepdims=True)
            accf = accf - (rowc + val("comp_col") - K * sc["mu"][0])
        if asym:
            zx, zw = sc["zx"][0], val("zw")
            accf = (accf - zw * rs_ref[...] - zx * val("colsum")
                    + K * zx * zw)
        out_ref[...] = accf * (sc["sx"][0] * val("sw"))


@functools.partial(jax.jit, static_argnames=("offset", "asym", "compensate"))
def onehot_qdot(x: jax.Array, qw: jax.Array, planes, scalars: dict,
                cols: dict, comp_r=None, *, offset: int = 0,
                asym: bool = True, compensate: bool = False) -> jax.Array:
    """The fused serving qdot as one Pallas call: float activations x
    (M, K), int32 weights qw (K, N) -> float32 (M, N).

    planes: product_planes' (256, 512) table (exact in bfloat16).
    scalars: name -> (1,) array: "kc" int32 (K*c), "sx", and as the mode
    needs "zx" (asym) and "mu" (compensation), then "sw"/"zw" when
    per-tensor (f32).  cols: name -> (1, N) f32 per-column epilogue
    parameters (of "sw", "zw", "colsum", "comp_col").  comp_r: the
    (256,) row compensation table, with ``compensate``.

    The kernel quantizes x with the static scale, selects the
    activations' rows of P - c with a one-hot contraction on the MXU,
    sums P[qx + off, qw + off] - c over K from those rows (by lane
    gather or by a one-hot of the weights, as ``contraction`` says;
    exact while K * max|P - c| < 2**31: K < 65,536 for every registered
    design) and applies the dequant epilogue before the tile leaves
    VMEM.  Its cost is the same whatever the table
    holds, and at the serving shapes no operand is padded or prepared
    in XLA: the call is the projection's only device op.
    """
    M, K = x.shape
    N = qw.shape[1]
    tm, tn, kb = _onehot_blocks(M, K, N)
    gather = tm <= _TG
    ks = _KS if gather else _KO
    Mp, Kp, Np = _ceil_mul(M, tm), _ceil_mul(K, kb), _ceil_mul(N, tn)
    nk = Kp // kb
    x = _pad_to(x.astype(jnp.float32), Mp, Kp)
    qw = _pad_to(qw.astype(jnp.int32), Kp, Np).reshape(Kp // ks, ks, Np)
    cols = {n: _pad_to(v, 1, Np) for n, v in cols.items()}
    snames, cnames = tuple(scalars), tuple(cols)
    in_specs = [
        pl.BlockSpec((tm, kb), lambda i, k, j, *_: (i, k)),
        pl.BlockSpec((kb // ks, ks, tn), lambda i, k, j, *_: (k, 0, j)),
        pl.BlockSpec((256, 512), lambda i, k, j, *_: (0, 0)),
    ]
    operands = [x, qw, jnp.asarray(planes, jnp.bfloat16)]
    if compensate:
        in_specs.append(pl.BlockSpec((1, 256), lambda i, k, j, *_: (0, 0)))
        operands.append(jnp.asarray(comp_r, jnp.float32).reshape(1, 256))
    in_specs += [pl.BlockSpec((1, tn), lambda i, k, j, *_: (0, j))
                 for _ in cnames]
    operands += [cols[n] for n in cnames]
    scratch = ([pltpu.VMEM((Np // tn, tm, tn), jnp.int32),
                pltpu.VMEM((tm, kb, 256), jnp.int32)] if gather else
               [pltpu.VMEM((Np // tn, 2 * tm, tn), jnp.float32),
                pltpu.VMEM((2 * tm, kb, 256), jnp.bfloat16)])
    if asym:
        scratch.append(pltpu.VMEM((tm, 1), jnp.float32))
    if compensate:
        scratch.append(pltpu.VMEM((tm, 256), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_onehot_kernel, scalars=snames, cols=cnames,
                          offset=offset, K=K, asym=asym,
                          compensate=compensate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(snames),
            grid=(Mp // tm, nk, Np // tn),
            in_specs=in_specs,
            # an output block is written back when the index changes:
            # before the last K block every step names the first tile
            out_specs=pl.BlockSpec(
                (tm, tn), lambda i, k, j, *_: (i, jnp.where(k == nk - 1,
                                                            j, 0))),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        compiler_params=platform.compiler_params(
            "parallel", "arbitrary", "arbitrary"),
        interpret=platform.pallas_interpret("onehot_qdot"),
        name="onehot_qdot",
    )(*[scalars[n] for n in snames], *operands)
    return out[:M, :N] if (Mp, Np) != (M, N) else out


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
