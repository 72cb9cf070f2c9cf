"""Public jit'd wrappers around the approximate-matmul kernels.

``approx_matmul`` is the operator the quantized layers call.  Backends:

  'delta'    — the two-stage fast path (bit-exact, recommended): exact
               int32 product + delta-table gather, as the blocked-XLA
               twin (ref.delta_matmul_ref) on every platform (the
               Pallas kernel's gather does not build for the TPU).  Any
               shape; the signed offset folds into the gather index (no
               operand pre-shift).
  'fused'    — the fused quantize->product->dequant serving path
               (``fused_qdot`` below; one one-hot kernel call on the
               TPU).  quant.linear dispatches to it when a
               QuantizedWeight carries calibrated static activation
               scales; integer-operand approx_matmul calls with
               backend='fused' fall back to 'delta' (same integer core,
               nothing to fuse without the float ends).
  'pallas'   — the delta Pallas kernel explicitly (interpret mode on
               the CPU, what the kernel tests exercise; refused on the
               TPU).
  'delta_xla'— the blocked-XLA twin explicitly (exact dot + K-blocked
               delta gather); what big-model graphs lower with in place
               of the old (M,K,N)-index-surface product-LUT gather.
  'pallas_legacy'
             — the original per-k LUT-gather Pallas kernel, kept for
               A/B benchmarking (benchmarks/run.py kernel_microbench;
               refused on the TPU).
  'xla'      — jnp.take product-LUT formulation (ref semantics); the
               dry-run path, lowers everywhere.
  'residual' — exact MXU matmul + rank-r correction (approximate
               emulation; r configurable; NOT bit-exact; refused on the
               TPU).
  'exact'    — plain integer matmul (the baseline multiplier).

All backends share a straight-through-estimator VJP: the backward pass
differentiates the *exact* product (standard QAT practice), so training
runs through the paper's multiplier in the forward pass only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import platform, ref
from .approx_matmul import delta_matmul, lut_matmul, residual_matmul
from .approx_matmul import onehot_qdot, product_planes

_LUT_CACHE: dict = {}


def get_lut(design: str) -> np.ndarray:
    """LUT for a registered multiplier design ('design1', 'design2', ...).

    'exact' returns the true product table."""
    if design not in _LUT_CACHE:
        from repro.core import lut as lutmod
        if design == "exact":
            a = np.arange(256, dtype=np.int64)
            _LUT_CACHE[design] = (a[:, None] * a[None, :]).astype(np.int32)
        else:
            _LUT_CACHE[design] = lutmod.build_lut(design)
    return _LUT_CACHE[design]


def get_signed_lut(design: str) -> np.ndarray:
    """Signed product LUT indexed [a+128, b+128] for a registered signed
    design (repro.signed.SIGNED_MULTIPLIERS; 'exact' = true product)."""
    key = ("signed", design)
    if key not in _LUT_CACHE:
        from repro.core import lut as lutmod
        _LUT_CACHE[key] = lutmod.build_signed_lut(design)
    return _LUT_CACHE[key]


def get_delta_lut(design: str, signed: bool = False) -> np.ndarray:
    """Delta table D = approx - exact for the two-stage kernel, int16
    where the design's error range allows (core.lut.build_delta_lut);
    'exact' is the all-zero table."""
    key = ("delta", design, signed)
    if key not in _LUT_CACHE:
        from repro.core import lut as lutmod
        _LUT_CACHE[key] = lutmod.build_delta_lut(design, signed)
    return _LUT_CACHE[key]


def get_factors(design: str, rank: int = 32, signed: bool = False):
    from repro.core import lut as lutmod
    if signed:
        F, G, _ = lutmod.signed_error_factors(design, rank)
    else:
        F, G, _ = lutmod.error_factors(design, rank)
    return F, G


# ---------------------------------------------------------------------------
# STE-wrapped approximate matmul
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def approx_matmul(a: jax.Array, b: jax.Array, design: str = "design2",
                  backend: str = "xla", rank: int = 32,
                  signed: bool = False) -> jax.Array:
    """S = A ⊗_approx B over int arrays. int32/float32 out.

    a: (..., M, K), b: (K, N). Batched over leading dims of `a`.
    Operands are uint8-valued ([0,255]) by default; with ``signed=True``
    they are int8-valued ([-128,127]) and the product routes through the
    signed multiplier registry (repro.signed) via offset-shifted LUTs.
    """
    return _approx_matmul_fwd_impl(a, b, design, backend, rank, signed)


def _approx_matmul_fwd_impl(a, b, design, backend, rank, signed=False):
    lead = a.shape[:-2]
    M = int(np.prod(lead)) * a.shape[-2] if lead else a.shape[-2]
    a2 = a.reshape(M, a.shape[-1])
    off = 128 if signed else 0
    lut = (lambda: get_signed_lut(design)) if signed \
        else (lambda: get_lut(design))
    if backend == "exact":
        out = ref.exact_matmul_ref(a2, b)
    elif backend == "xla":
        # Faithful gather formulation. NB: materializes the (M,K,N) index
        # surface unless XLA fuses it — fine at test/benchmark scale; the
        # big-model graphs use 'delta' (same bits, K-blocked gather).
        out = ref.approx_matmul_ref(a2, b, lut(), offset=off)
    elif backend in ("pallas", "delta", "delta_xla", "fused"):
        # Two-stage delta path: exact MXU product + delta gather.
        # Signed operands index the table via the folded-in offset; no
        # pre-shift pass, and shapes need not be block multiples.
        # 'delta' (and 'fused', which on integer operands has no float
        # ends to fuse) runs the XLA twin; 'pallas' the gather kernel.
        if backend == "pallas":
            out = delta_matmul(a2, b,
                               jnp.asarray(get_delta_lut(design, signed)),
                               offset=off)
        else:
            out = ref.delta_matmul_ref(a2, b, get_delta_lut(design, signed),
                                       offset=off)
    elif backend == "pallas_legacy":
        # The legacy LUT kernel is offset-free: int8 operands are
        # pre-shifted to the [0,255] index domain of the signed table.
        out = lut_matmul(a2.astype(jnp.int32) + off,
                         b.astype(jnp.int32) + off, jnp.asarray(lut()))
    elif backend == "residual":
        F, G = get_factors(design, rank, signed)
        out = residual_matmul(a2, b, jnp.asarray(F), jnp.asarray(G),
                              offset=off)
    elif backend == "residual_xla":
        # Pure-XLA rank-r emulation: exact MXU matmul + einsum correction.
        # This is what the production-mesh graphs lower with.
        F, G = get_factors(design, rank, signed)
        out = ref.residual_corrected_matmul_ref(a2, b, jnp.asarray(F),
                                                jnp.asarray(G), offset=off)
    else:
        raise ValueError(backend)
    # float32 output so the STE custom_vjp has a nontrivial tangent space
    # (int32 outputs have no gradient).  NB: sums beyond 2^24 lose ULPs in
    # f32 — irrelevant at NN noise level, asserted bounded in tests.
    out = out.astype(jnp.float32)
    return out.reshape(*lead, a.shape[-2], b.shape[-1])


def _approx_matmul_fwd(a, b, design, backend, rank, signed):
    return _approx_matmul_fwd_impl(a, b, design, backend, rank, signed), (a, b)


def _approx_matmul_bwd(design, backend, rank, signed, res, g):
    a, b = res
    g = g.astype(jnp.float32)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    da = jnp.matmul(g, bf.T)
    lead = a.shape[:-2]
    g2 = g.reshape(-1, g.shape[-1])
    a2 = af.reshape(-1, af.shape[-1])
    db = jnp.matmul(a2.T, g2)
    return da, db


approx_matmul.defvjp(_approx_matmul_fwd, _approx_matmul_bwd)


def approx_mul(a: jax.Array, b: jax.Array, design: str = "design2",
               signed: bool = False) -> jax.Array:
    """Elementwise approximate product (used by the image pipelines)."""
    if signed:
        return ref.approx_mul_ref(a, b, get_signed_lut(design), offset=128)
    return ref.approx_mul_ref(a, b, get_lut(design))


# ---------------------------------------------------------------------------
# Fused decode-step attention/cache op
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array, idx: jax.Array,
                     *, n_heads: int, n_kv: int, head_dim: int,
                     rope_theta: float = 10000.0, window=None,
                     q_gain=None, k_gain=None, block_s: int = 128,
                     lowering: str = "auto"):
    """The fused decode-step attention/cache op: qk-norm + rope at the
    slot's cache position + KV-cache append + masked single-query GQA
    attention (the step-level twin of ``fused_qdot``).

    q: (B, 1, n_heads, hd) pre-norm pre-rope; k/v: (B, 1, n_kv, hd).
    idx: scalar int32 (uniform decode) or (B,) int32 per-slot cache
    positions (batched multi-slot decode — the continuous-batching
    loop's schedule).  ``lowering``: 'auto' (kernels.platform: the
    Pallas kernel on the TPU, the bit-matched XLA twin elsewhere),
    'pallas', or 'xla'.  Both lowerings share the norm/rope/append ops,
    so the returned caches are identical; the attention output agrees
    to f32 reassociation ULPs.

    Returns (out (B, 1, n_heads*hd) f32, k_cache', v_cache').
    """
    idx = jnp.asarray(idx)
    if platform.lowering("decode_attention", lowering) == "xla":
        return ref.decode_attention_ref(
            q, k, v, k_cache, v_cache, idx, n_heads=n_heads, n_kv=n_kv,
            head_dim=head_dim, rope_theta=rope_theta, window=window,
            q_gain=q_gain, k_gain=k_gain)
    from .attention import decode_attention_step
    B = q.shape[0]
    q, k = ref.decode_rows(q, k, idx, rope_theta=rope_theta,
                           q_gain=q_gain, k_gain=k_gain)
    # the row append is a (B, 1, Kv, hd) write — in place when the
    # caller donates the cache buffers, as the TPU serve step does
    ck, cv = ref.append_rows(k_cache, v_cache, k, v, idx)
    pos = jnp.broadcast_to(idx.reshape(-1), (B,))
    out = decode_attention_step(
        q.reshape(B, n_heads, head_dim), ck, cv, pos,
        group=n_heads // max(n_kv, 1), window=window, block_s=block_s)
    return out.reshape(B, 1, n_heads * head_dim), ck, cv


# ---------------------------------------------------------------------------
# Fused quantize -> delta -> dequant serving entry point
# ---------------------------------------------------------------------------

def _as_col(v, N: int):
    """Normalize a scalar / (1,N) / (N,) epilogue parameter to (N,) f32
    (per-tensor values broadcast; elementwise epilogue math is then
    bit-identical to the scalar-broadcast unfused pipeline)."""
    if v is None:
        return jnp.zeros((N,), jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    return jnp.broadcast_to(v.reshape(-1) if v.ndim else v, (N,))


def fused_qdot(x: jax.Array, qw: jax.Array, dlut: jax.Array, *,
               dlut_idx=None, sx, zx=None, sw, zw=None, colsum=None,
               comp_r=None, comp_col=None, comp_mu=None,
               signed: bool = False, compensate: bool = False,
               k_block: int = 32, lowering: str = "auto") -> jax.Array:
    """The fused serving qdot: float x (..., K) @ prequantized qw (K, N)
    -> float32 (..., N), with static-scale activation quantization, the
    approximate integer product (``dlut`` as an operand), and the
    dequant epilogue.

    dlut: (256, 256) delta table, or a stacked (L, 256, 256) BANK with
    ``dlut_idx`` a scalar int32 layer index (the mixed-design plan
    path: quant.linear.register_dlut_bank keeps the bank out of the
    layer scan; the index selects the table before the one-hot kernel
    splits it into planes, and folds into the gather base on the XLA
    twin).  sx/zx: calibrated static activation scale / zero point (zx
    None for sym_i8).  sw/zw: weight scale / zero point — scalar
    (per-tensor) or (1, N)/(N,) (per-channel).  colsum: colsum(qw) for
    the asym_u8 zero-point cross term.  comp_*: mean-field compensation
    tables (row table (256,), precomputed column colsum (N,), scalar
    mean) when ``compensate``.  ``lowering`` (kernels.platform): 'auto'
    (the one-hot contraction on the TPU, the blocked-XLA twin
    elsewhere), 'pallas' (the one-hot kernel, in interpret mode on the
    CPU) or 'xla' (the twin, ``k_block`` K rows per gather step).  The
    kernel applies the twin's quantizer and epilogue
    (ref.fused_qdot_ref) op for op; the integer product is bit-exact in both,
    and with compensation the row table's sum (a histogram of qx against
    the table in the kernel) differs by float reassociation only.  Each call traced under a
    step adds one to obs.QDOT_LOWERING_ONEHOT or obs.QDOT_LOWERING_XLA.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = qw.shape[-1]
    x2 = x.reshape(-1, K)
    off = 128 if signed else 0
    layer = (jnp.asarray(dlut_idx, jnp.int32).reshape(())
             if dlut_idx is not None else None)
    onehot = platform.lowering("qdot", lowering) == "pallas"
    if isinstance(x, jax.core.Tracer):
        obs.count(obs.QDOT_LOWERING_ONEHOT if onehot
                  else obs.QDOT_LOWERING_XLA)
    if onehot:
        planes, c = product_planes(dlut, layer, off)
        scalars = {"kc": jnp.asarray(K * c, jnp.int32).reshape(1)}
        cols = {}

        def param(name, v):
            # as it comes (reshapes only), so that no op prepares it
            v = jnp.asarray(0.0 if v is None else v, jnp.float32)
            if v.size == 1:
                scalars[name] = v.reshape(1)
            else:
                cols[name] = v.reshape(1, N)
        param("sx", sx)
        param("sw", sw)
        if not signed:
            for name, v in (("zx", zx), ("zw", zw), ("colsum", colsum)):
                param(name, v)
        if compensate:
            param("mu", comp_mu)
            param("comp_col", comp_col)
        out = onehot_qdot(x2, qw, planes, scalars, cols,
                          comp_r if compensate else None, offset=off,
                          asym=not signed, compensate=compensate)
    else:
        scal = jnp.stack([jnp.asarray(v, jnp.float32).reshape(())
                          for v in (sx, 0.0 if zx is None else zx,
                                    0.0 if comp_mu is None else comp_mu)])
        ntab = jnp.stack([_as_col(sw, N), _as_col(zw, N),
                          _as_col(colsum, N), _as_col(comp_col, N)])
        cr = (jnp.asarray(comp_r, jnp.float32).reshape(-1)
              if comp_r is not None else jnp.zeros((256,), jnp.float32))
        out = ref.fused_qdot_ref(x2, qw, dlut, scal, ntab, cr, offset=off,
                                 asym=not signed, compensate=compensate,
                                 k_block=k_block, layer=layer)
    return out.reshape(*lead, N)
