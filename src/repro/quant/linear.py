"""Quantized linear ops routed through the approximate multiplier.

``qdot(x, w, cfg)`` is THE integration point of the paper's technique:
every dense projection in every architecture goes through it.  With
cfg.design == 'exact' it is a plain fp matmul (the baseline); otherwise
the uint8 zero-point decomposition sends the Q_x ⊗ Q_w term through the
selected approximate-multiplier backend.

Shardability: qdot is pure jnp/custom_vjp; under pjit the operand
shardings propagate through quantize (elementwise), the LUT gather
(batched take — replicated table), and the matmul terms, so the same
code paths run on the 2x16x16 production mesh (verified by the dry-run).

Precomputation ladder (each rung drops per-call work from the jitted
decode step; all are carried by ``QuantizedWeight``, a pytree that rides
jax.lax.scan over stacked layers/experts in lockstep with the weights):

  1. weight prequantization (``prequantize_weights``) — cached
     (q, scale, zp) + the colsum of q (the zero-point cross term of the
     asym_u8 decomposition), so a decode step pays no weight min/max/
     round/clip/reduce work.  Per-tensor or per-output-channel scales
     (QuantConfig.w_per_channel).
  2. static activation scales (``repro.calib``: observe -> table ->
     ``apply_calibration``) — fixed per-layer (scale, zp) for the
     activation quantizer, dropping the per-token min/max reduction.
  3. per-layer design plans (``repro.calib.plan``) — a stacked delta
     LUT (+ mean-field compensation tables) per layer, so the scanned
     decode body computes exact-MXU-product + delta-gather against its
     own layer's multiplier design (heterogeneous deployment).

The cached (q, scale, zp) are value-identical to what on-the-fly
quantization computes (per scan slice), so outputs agree to
float-reduction ULPs — the two graph shapes may fuse float sums
differently — and greedy decode tokens match.  The master weights ride
along for the STE/exact branches of training and QAT; the serving form
(prequantized under an inference QuantConfig) drops them.

Calibration observers: ``repro.calib.observe`` installs a process-global
observer via ``set_observer``; qdot reports (x, site, cfg) for every
QuantizedWeight-bound call.  Observation runs eagerly with the unit
scans unrolled (calib.observe.pscan), so the observer sees concrete
per-layer values and names sites by the weight's tree path + scan
indices.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from .quantize import QuantConfig, quantize_int8, quantize_uint8

_MF_CACHE: dict = {}

# Param-dict keys that flow through qdot (models/): every dense kernel
# is named "w*" ("wq", "w_up", "wo_gate", ...) plus the MoE router and
# the encoder frontend projection.  Norm gains, embeddings, conv stems
# deliberately do NOT match.
_DENSE_KEYS = ("router", "frontend_proj")

# Calibration observer (repro.calib.observe).  None outside calibration
# passes; when set, qdot reports every QuantizedWeight-bound call.
_OBSERVER = None

# Stale-cache warning dedup: one warning per (cached, requested) pair
# per process, not one per call site per trace.
_STALE_WARNED: set = set()

# Delta-table banks (repro.calib.plan): per-site STACKED per-layer delta
# tables, registered once at plan-install time and closed over as jit
# CONSTANTS by qdot.  Keys are content-addressed (path + mode + design
# list), so re-registering is idempotent and two plans only collide when
# they would install identical tables anyway.
_DLUT_BANKS: dict = {}


def register_dlut_bank(key: str, bank) -> None:
    """Register a site's stacked (L, 256, 256) delta-table bank.  The
    per-layer wrapper then carries only an int32 index into it
    (QuantizedWeight.dlut with aux dlut_bank=key): the 256 KiB tables
    stay out of the layer scan's sliced params entirely."""
    _DLUT_BANKS[key] = jnp.asarray(bank).reshape(-1, 256, 256)


def get_dlut_bank(key: str):
    if key not in _DLUT_BANKS:
        raise KeyError(
            f"delta-table bank {key!r} is not registered in this process "
            f"({len(_DLUT_BANKS)} banks known).  QuantizedWeight trees "
            f"carrying bank indices are process-local: re-run "
            f"calib.plan.apply_plan (or make_plan_injector) to install "
            f"the plan here.")
    return _DLUT_BANKS[key]


def set_observer(obs) -> None:
    """Install (or clear, with None) the calibration observer."""
    global _OBSERVER
    _OBSERVER = obs


def get_observer():
    return _OBSERVER


@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """A dense weight with (some of) its quantization precomputed.

    Transparent to qdot: pass one where a float (…, K, N) weight went.
    Carries the master weights ``w`` (STE / cfg.enabled=False branches)
    alongside optional cached fields; leading (stacked-layer / expert)
    axes are preserved on every field so jax.lax.scan slices them all in
    lockstep with per-slice values identical to on-the-fly computation.

    The SERVING form has ``w`` None: an inference-mode qdot reads only
    the cached quantization, so prequantize_weights under an inference
    QuantConfig keeps no float master (at qwen3-1.7b widths the masters
    are 5.6 GB the chip would otherwise hold twice).

    Fields (None = not precomputed; qdot falls back to dynamic work):
      q, scale, zp  cached weight quantization (zp None for sym_i8);
                    per-channel scales have shape (…, 1, N)
      colsum        colsum(q) float32 (…, 1, N) — the asym_u8 zero-point
                    cross term, cached so decode skips an O(K·N) reduce
      act_scale/act_zp
                    calibrated STATIC activation quantizer (…,) — drops
                    the per-token min/max reduction (repro.calib.static)
      dlut          the mixed-design plan path (repro.calib.plan):
                    exact product + gather of THIS layer's design
                    error.  Either a per-layer delta table
                    (…, 256, 256) int16/int32, or — when the aux field
                    ``dlut_bank`` names a registered table bank — a
                    per-layer int32 INDEX (… ,) into that bank.  The
                    bank form is what apply_plan installs: the stacked
                    tables stay OUT of the scan-sliced params (a 256 KiB
                    dynamic-slice per site per layer per step,
                    measured ~60%% of the plan-path decode step on CPU)
                    and ride the jitted body as one constant; only the
                    scalar index rides the scan
      comp_r/comp_c/comp_mu
                    per-layer mean-field compensation tables matching
                    dlut's designs (used when cfg.compensate)
      comp_col      cached colsum of the column compensation table over
                    the quantized weight, (…, 1, N) f32 — drops the
                    per-call O(K·N) take(comp_c, q) gather from the
                    fused epilogue (calib.plan.apply_plan /
                    calib.static.attach_comp_cols)

    Static metadata (pytree aux, preserved by scan/vmap slicing):
      mode          QuantConfig.mode the cache was built for
      path          the weight's params-tree path ("units.0.attn.wq") —
                    the calibration site name
      per_channel   weight-scale granularity of q/scale/zp
      dlut_bank     registry key (register_dlut_bank) of the site's
                    stacked delta-table bank; dlut is then an index
    """

    def __init__(self, w, q=None, scale=None, zp=None, colsum=None,
                 act_scale=None, act_zp=None, dlut=None,
                 comp_r=None, comp_c=None, comp_mu=None, comp_col=None,
                 mode: str = "asym_u8", path: str = "",
                 per_channel: bool = False, dlut_bank=None,
                 merged: bool = False):
        self.w = w
        self.q = q
        self.scale = scale
        self.zp = zp          # None for symmetric (sym_i8) quantization
        self.colsum = colsum
        self.act_scale = act_scale
        self.act_zp = act_zp
        self.dlut = dlut
        self.comp_r = comp_r
        self.comp_c = comp_c
        self.comp_mu = comp_mu
        self.comp_col = comp_col
        self.mode = mode
        self.path = path
        self.per_channel = per_channel
        self.dlut_bank = dlut_bank
        # fuse_projections output: scales are stored per-column (a
        # blockwise broadcast of the member projections' scales), so the
        # per_channel flag intentionally differs from the serving
        # QuantConfig — the stale-cache check skips merged wrappers
        self.merged = merged

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def shape(self):
        return (self.w if self.w is not None else self.q).shape

    def replace(self, **kw) -> "QuantizedWeight":
        d = dict(w=self.w, q=self.q, scale=self.scale, zp=self.zp,
                 colsum=self.colsum, act_scale=self.act_scale,
                 act_zp=self.act_zp, dlut=self.dlut, comp_r=self.comp_r,
                 comp_c=self.comp_c, comp_mu=self.comp_mu,
                 comp_col=self.comp_col, mode=self.mode,
                 path=self.path, per_channel=self.per_channel,
                 dlut_bank=self.dlut_bank, merged=self.merged)
        d.update(kw)
        return QuantizedWeight(**d)

    def tree_flatten(self):
        children = (self.w, self.q, self.scale, self.zp, self.colsum,
                    self.act_scale, self.act_zp, self.dlut,
                    self.comp_r, self.comp_c, self.comp_mu, self.comp_col)
        return children, (self.mode, self.path, self.per_channel,
                          self.dlut_bank, self.merged)

    @classmethod
    def tree_unflatten(cls, aux, children):
        mode, path, per_channel, dlut_bank, merged = aux
        return cls(*children, mode=mode, path=path, per_channel=per_channel,
                   dlut_bank=dlut_bank, merged=merged)

    def __repr__(self):
        extras = [k for k in ("act_scale", "dlut")
                  if getattr(self, k) is not None]
        return (f"QuantizedWeight(shape={tuple(self.shape)}, "
                f"mode={self.mode!r}, path={self.path!r}, "
                f"per_channel={self.per_channel}"
                + (f", +{'/'.join(extras)}" if extras else "") + ")")


def _weight_axis(w, per_channel: bool):
    """Quantization reduce axes over the trailing (K, N): all of them
    (per-tensor — one scale per stacked slice) or K only (per-channel —
    one scale per output column, shape (…, 1, N))."""
    if per_channel:
        return w.ndim - 2
    return None if w.ndim == 2 else tuple(range(w.ndim - 2, w.ndim))


def _quantize_weight(w: jax.Array, cfg: QuantConfig,
                     path: str = "") -> QuantizedWeight:
    """Quantize over the trailing (K, N) axes; leading axes are stacked
    layers/experts and keep their own scales (matching what on-the-fly
    qdot computes per scan slice).  The master rides along unless
    ``cfg.inference`` (the serving form)."""
    axis = _weight_axis(w, cfg.w_per_channel)
    if cfg.signed:
        q, s = quantize_int8(w, axis)
        zp = colsum = None
    else:
        q, s, zp = quantize_uint8(w, axis)
        colsum = q.sum(axis=-2, keepdims=True).astype(jnp.float32)
    return QuantizedWeight(None if cfg.inference else w, q, s, zp,
                           colsum=colsum, mode=cfg.mode, path=path,
                           per_channel=cfg.w_per_channel)


def is_dense_weight(k, v) -> bool:
    """Does params-tree key k with value v flow through qdot?"""
    return ((k in _DENSE_KEYS or (isinstance(k, str) and k.startswith("w")))
            and isinstance(v, jax.Array) and v.ndim >= 2
            and jnp.issubdtype(v.dtype, jnp.floating))


def map_quantized(node, fn):
    """Rebuild a params tree applying fn(qw) -> QuantizedWeight to every
    QuantizedWeight node (the shared install traversal of
    calib.static/calib.plan)."""
    if isinstance(node, QuantizedWeight):
        return fn(node)
    if isinstance(node, dict):
        return {k: map_quantized(v, fn) for k, v in node.items()}
    if isinstance(node, (list, tuple)):
        return type(node)(map_quantized(v, fn) for v in node)
    return node


def walk_dense(node, fn, path=""):
    """Rebuild a params tree applying fn(leaf, path) to every qdot-bound
    dense weight (the shared traversal of prequantize/calib/plan)."""
    if isinstance(node, dict):
        return {k: (fn(v, f"{path}.{k}".lstrip("."))
                    if is_dense_weight(k, v)
                    else walk_dense(v, fn, f"{path}.{k}".lstrip(".")))
                for k, v in node.items()}
    if isinstance(node, (list, tuple)):
        return type(node)(walk_dense(v, fn, f"{path}.{i}".lstrip("."))
                          for i, v in enumerate(node))
    return node


def prequantize_weights(params, cfg: QuantConfig, *, consume: bool = False):
    """Return a copy of ``params`` with every qdot-bound dense weight
    wrapped in a QuantizedWeight (call once, outside jit).

    Each wrapper records its tree path (the calibration site name used
    by repro.calib).  No-op when cfg.enabled is False.  Used by
    launch/serve.py (--prequantize) to drop per-decode-step weight
    quantization.  Under an inference ``cfg`` the wrappers hold no
    float master (the serving form).

    ``consume=True`` frees each float weight as soon as its quantized
    form exists, so the float and quantized layer weights are never
    resident together (one leaf at a time is); ``params`` must not be
    used afterwards.  serve.prepare_params consumes the tree it is
    given.
    """
    if not cfg.enabled:
        return params

    def wrap(v, path):
        qw = _quantize_weight(v, cfg, path)
        if consume and qw.w is None:
            v.delete()
        return qw
    return walk_dense(params, wrap)


def _warn_stale(pre: QuantizedWeight, cfg: QuantConfig) -> None:
    key = (pre.mode, pre.per_channel, cfg.mode, cfg.w_per_channel)
    if key in _STALE_WARNED:
        return
    _STALE_WARNED.add(key)
    warnings.warn(
        f"QuantizedWeight cache built for mode={pre.mode!r}/"
        f"per_channel={pre.per_channel} used with "
        f"QuantConfig(mode={cfg.mode!r}, w_per_channel="
        f"{cfg.w_per_channel}) (site {pre.path!r}): falling back to "
        f"requantizing the master weights on EVERY call, which erases "
        f"the prequantize speedup.  Re-run prequantize_weights with the "
        f"serving QuantConfig.", stacklevel=3)


def _mean_field_tables(design: str, signed: bool = False):
    """Conditional-mean error tables for bias compensation (float32).

    Cached as numpy (never as traced/device values) so the cache is safe
    to populate inside jit/scan tracing.  Signed tables are indexed by
    the offset-shifted operand (q + 128)."""
    key = (design, signed)
    if key not in _MF_CACHE:
        from repro.core import lut as lutmod
        import numpy as np
        table = (lutmod.signed_error_table if signed
                 else lutmod.error_table)
        e = table(design).astype(np.float64)
        _MF_CACHE[key] = (e.mean(1).astype(np.float32),
                          e.mean(0).astype(np.float32),
                          float(e.mean()))
    mu_r, mu_c, mu = _MF_CACHE[key]
    return jnp.asarray(mu_r), jnp.asarray(mu_c), jnp.float32(mu)


def _site_comp_tables(pre, cfg: QuantConfig, signed: bool):
    """Compensation tables: the per-layer ones attached by a design plan
    (matching the layer's dlut design) when present, else the static
    per-design tables."""
    if pre is not None and pre.comp_r is not None:
        return (pre.comp_r, pre.comp_c,
                pre.comp_mu.reshape(()).astype(jnp.float32))
    return _mean_field_tables(cfg.design, signed=signed)


def _wparam(p, per_channel: bool):
    """Reshape a cached weight-quant parameter for broadcast: a
    scan-sliced per-tensor (1, 1) scale must broadcast EXACTLY like the
    on-the-fly scalar so the lowered graph (and its float rounding) is
    bit-identical; per-channel scales keep their (1, N) column shape."""
    if p is None:
        return None
    if per_channel:
        return p.reshape(1, p.shape[-1])
    return p.reshape(())


def _delta_prod(qx, qw, pre, offset: int):
    """Per-layer mixed-design product: exact int32 matmul + gather of
    the layer's OWN delta table, i.e. the two-stage decomposition with
    a data-driven stage-2 table.  Bank-registered plans gather straight
    from the constant bank with the scan-sliced layer index folded into
    the gather base; legacy table-carrying wrappers fall back to the
    blocked-XLA delta twin with the traced table."""
    from repro.kernels import ref
    lead = qx.shape[:-1]
    a2 = qx.reshape(-1, qx.shape[-1])
    if pre.dlut_bank is not None:
        out = ref.delta_matmul_ref(a2, qw, get_dlut_bank(pre.dlut_bank),
                                   offset=offset,
                                   layer=pre.dlut.reshape(()))
    else:
        out = ref.delta_matmul_ref(a2, qw, pre.dlut, offset=offset)
    return out.reshape(*lead, qw.shape[-1])


def _use_fused(cfg: QuantConfig, pre) -> bool:
    """backend='fused' dispatches to the one-kernel quantize->delta->
    dequant path whenever the wrapper carries everything the kernel
    needs precomputed: cached weight quantization AND calibrated static
    activation scales.  Otherwise qdot falls through to the unfused
    pipeline (whose product backend treats 'fused' as 'delta')."""
    return (cfg.backend == "fused" and pre is not None
            and pre.q is not None and pre.act_scale is not None)


def _qdot_fused(x, pre, cfg: QuantConfig, signed: bool):
    """Assemble the fused kernel's operands from a QuantizedWeight and
    dispatch (kernels.ops.fused_qdot: Pallas on TPU, blocked-XLA twin
    elsewhere).  The delta table is the per-layer plan slice when the
    wrapper carries one (pre.dlut — a traced scan slice riding the same
    jitted body), else the serving design's static table."""
    from repro.kernels import ops
    off = 128 if signed else 0
    dlut_idx = None
    if pre.dlut_bank is not None:
        dlut = get_dlut_bank(pre.dlut_bank)
        dlut_idx = pre.dlut.reshape(())
    elif pre.dlut is not None:
        dlut = pre.dlut
    else:
        # numpy, so a lowering may prepare it while tracing
        dlut = ops.get_delta_lut(cfg.design, signed)
    comp_r = comp_col = comp_mu = None
    if cfg.compensate:
        comp_r, comp_c, comp_mu = _site_comp_tables(pre, cfg, signed)
        if pre.comp_col is not None:
            comp_col = pre.comp_col.reshape(-1)
        else:
            comp_col = jnp.take(comp_c, pre.q + off, axis=0).sum(0)
    return ops.fused_qdot(
        x, pre.q, dlut, dlut_idx=dlut_idx,
        sx=pre.act_scale.reshape(()),
        zx=(pre.act_zp.reshape(()) if pre.act_zp is not None else None),
        sw=_wparam(pre.scale, pre.per_channel),
        zw=_wparam(pre.zp, pre.per_channel),
        colsum=(pre.colsum.reshape(-1) if pre.colsum is not None else None),
        comp_r=comp_r, comp_col=comp_col, comp_mu=comp_mu,
        signed=signed, compensate=cfg.compensate)


def qdot(x: jax.Array, w: jax.Array, cfg: QuantConfig) -> jax.Array:
    """y[..., n] = sum_k approx(x[..., k], w[k, n])  (dequantized float32).

    x: (..., K) float; w: (K, N) float master weights, or a
    QuantizedWeight (prequantize_weights / repro.calib) carrying any of:
    cached weight quantization, calibrated static activation scales, a
    per-layer design plan (delta table).  Runs under the device scope
    qdot.<last part of the wrapper's path> (obs.qdot_scope).
    """
    pre = w if isinstance(w, QuantizedWeight) else None
    with obs.scope(obs.qdot_scope(pre.path if pre is not None else ""),
                   x):
        return _qdot(x, w, pre, cfg)


def _qdot(x, w, pre, cfg: QuantConfig):
    if pre is not None:
        w = pre.w
        if pre.mode != cfg.mode or (
                pre.q is not None and not pre.merged
                and pre.per_channel != cfg.w_per_channel):
            if w is None:
                raise ValueError(
                    f"QuantizedWeight cache built for mode={pre.mode!r}/"
                    f"per_channel={pre.per_channel} used with "
                    f"QuantConfig(mode={cfg.mode!r}, w_per_channel="
                    f"{cfg.w_per_channel}) (site {pre.path!r}): this "
                    f"serving-form wrapper has no master weights to "
                    f"requantize from.  Prepare the tree with the "
                    f"serving QuantConfig.")
            _warn_stale(pre, cfg)   # loud: requantizing every step
            pre = None
    if _OBSERVER is not None and pre is not None:
        _OBSERVER.record(x, pre, cfg)
    if w is None and not (cfg.enabled and cfg.inference):
        raise ValueError(
            f"serving-form QuantizedWeight (site {pre.path!r}) has no "
            f"master weights: it serves only an inference QuantConfig "
            f"with an approximate design (got design={cfg.design!r}, "
            f"inference={cfg.inference})")
    if not cfg.enabled:
        return jnp.matmul(x, w)
    if cfg.signed:
        y = _qdot_signed(x, w, cfg, pre)
    else:
        y = _qdot_asym(x, w, cfg, pre)
    if cfg.inference:
        # Pure inference (serve): the STE trick below evaluates to y
        # anyway (y_ste + (y - y_ste)); skipping it halves decode FLOPs
        # at the cost of float-reassociation ULPs on the output.
        return y
    # STE: gradient flows as if y == x @ w  (exact fp product)
    y_ste = jnp.matmul(x, w)
    return y_ste + jax.lax.stop_gradient(y - y_ste)


def _act_axis(x, cfg: QuantConfig):
    """Reduce axes for DYNAMIC activation quantization.  Default: all
    axes (one scale per call — what the token-by-token decode step
    computes over its (B, 1, K) block).  With cfg.act_per_pos and a
    sequence axis present, every axis EXCEPT the sequence one, so a
    full-sequence prefill gives each position the scale its own decode
    step would have computed (bit-identical handoff; train.step)."""
    if cfg.act_per_pos and x.ndim >= 3:
        return tuple(i for i in range(x.ndim) if i != x.ndim - 2)
    return None


def _quantize_act_static(x, pre, lo, hi):
    """Quantize activations with the calibrated STATIC (scale, zp): no
    per-token min/max reduction in the decode graph."""
    sx = pre.act_scale.reshape(())
    zx = (pre.act_zp.reshape(()) if pre.act_zp is not None
          else jnp.float32(0.0))
    qx = jnp.clip(jnp.round(x / sx) + zx, lo, hi).astype(jnp.int32)
    return qx, sx, zx


def _qdot_asym(x, w, cfg, pre=None):
    """Paper-faithful uint8 path: zero-point decomposition around the
    unsigned approximate product."""
    if _use_fused(cfg, pre):
        return _qdot_fused(x, pre, cfg, signed=False)
    if pre is not None and pre.act_scale is not None:
        qx, sx, zx = _quantize_act_static(x, pre, 0, 255)
    else:
        qx, sx, zx = quantize_uint8(x, _act_axis(x, cfg))
    if pre is not None and pre.q is not None:
        qw = pre.q
        sw = _wparam(pre.scale, pre.per_channel)
        zw = _wparam(pre.zp, pre.per_channel)
        colsum = pre.colsum.reshape(1, pre.colsum.shape[-1]) \
            if pre.colsum is not None else None
    else:
        qw, sw, zw = quantize_uint8(w, _weight_axis(w, cfg.w_per_channel))
        if cfg.w_per_channel:
            sw, zw = _wparam(sw, True), _wparam(zw, True)
        colsum = None
    K = x.shape[-1]
    if pre is not None and pre.dlut is not None:
        prod = _delta_prod(qx, qw, pre, offset=0)
    else:
        prod = ops.approx_matmul(qx, qw, cfg.design, cfg.backend, cfg.rank)
    prod = prod.astype(jnp.float32)
    if cfg.compensate:
        mu_r, mu_c, mu = _site_comp_tables(pre, cfg, signed=False)
        comp = (jnp.take(mu_r, qx, axis=0).sum(-1, keepdims=True)
                + jnp.take(mu_c, qw, axis=0).sum(0, keepdims=True)
                - K * mu)
        prod = prod - comp
    rowsum = qx.sum(axis=-1, keepdims=True).astype(jnp.float32)    # (..., 1)
    if colsum is None:
        colsum = qw.sum(axis=0, keepdims=True).astype(jnp.float32)  # (1, N)
    y = prod - zw * rowsum - zx * colsum + K * zx * zw
    return y * (sx * sw)


def _qdot_signed(x, w, cfg, pre=None):
    """Symmetric int8 hot path: Q_x ⊗_signed Q_w straight through the
    signed backend — no zero-point cross-term matmuls."""
    if _use_fused(cfg, pre):
        return _qdot_fused(x, pre, cfg, signed=True)
    if pre is not None and pre.act_scale is not None:
        qx, sx, _ = _quantize_act_static(x, pre, -128, 127)
    else:
        qx, sx = quantize_int8(x, _act_axis(x, cfg))
    if pre is not None and pre.q is not None:
        qw, sw = pre.q, _wparam(pre.scale, pre.per_channel)
    else:
        qw, sw = quantize_int8(w, _weight_axis(w, cfg.w_per_channel))
        if cfg.w_per_channel:
            sw = _wparam(sw, True)
    K = x.shape[-1]
    if pre is not None and pre.dlut is not None:
        prod = _delta_prod(qx, qw, pre, offset=128)
    else:
        prod = ops.approx_matmul(qx, qw, cfg.design, cfg.backend, cfg.rank,
                                 True)
    prod = prod.astype(jnp.float32)
    if cfg.compensate:
        mu_r, mu_c, mu = _site_comp_tables(pre, cfg, signed=True)
        comp = (jnp.take(mu_r, qx + 128, axis=0).sum(-1, keepdims=True)
                + jnp.take(mu_c, qw + 128, axis=0).sum(0, keepdims=True)
                - K * mu)
        prod = prod - comp
    return prod * (sx * sw)


def _bcast_col(p, lead, n: int):
    """Broadcast a cached weight-quant parameter to an explicit
    per-column (…, 1, n) table (per-tensor scalars fan out; per-channel
    rows pass through)."""
    if p is None:
        return None
    p = jnp.asarray(p)
    return jnp.broadcast_to(p.reshape(*lead, 1, -1), (*lead, 1, n))


def _merge_group(parts, name: str):
    """Concatenate a group of prequantized SAME-INPUT projections into
    one QuantizedWeight along the output axis, or return None when the
    group is not safely mergeable.  Per-column epilogue parameters
    (scale/zp/colsum/comp_col) keep each member's values on its own
    column block, so the merged qdot output is bit-identical per column
    to the separate calls (asserted in tests/test_decode_attention.py).
    """
    import numpy as np
    if not all(isinstance(p, QuantizedWeight) and p.q is not None
               for p in parts):
        return None
    lead = tuple(int(d) for d in parts[0].shape[:-2])
    K = parts[0].shape[-2]
    if any(p.mode != parts[0].mode or tuple(p.shape[:-2]) != lead
           or p.shape[-2] != K for p in parts):
        return None
    # the members consume the SAME activations, so calibrated static
    # quantizers must agree — they do by construction (same observer
    # input), but a hand-edited tree might differ: refuse, don't drift
    acts = [p.act_scale for p in parts]
    if any((a is None) != (acts[0] is None) for a in acts):
        return None
    if acts[0] is not None and not all(
            np.array_equal(np.asarray(a), np.asarray(acts[0]))
            for a in acts[1:]):
        return None
    # per-layer design plans: mergeable only when every member gathers
    # the same delta table on every layer (one table per fused call)
    dluts = [p.dlut for p in parts]
    if any(d is not None for d in dluts):
        if any(d is None or p.dlut_bank is None
               for d, p in zip(dluts, parts)):
            return None
        banks = [np.asarray(get_dlut_bank(p.dlut_bank)) for p in parts]
        idxs = [np.asarray(p.dlut).reshape(-1) for p in parts]
        for li in range(idxs[0].size):
            t0 = banks[0][idxs[0][li]]
            if not all(np.array_equal(b[i[li]], t0)
                       for b, i in zip(banks[1:], idxs[1:])):
                return None
    ns = [int(p.shape[-1]) for p in parts]
    comp_cols = [p.comp_col for p in parts]
    merged_comp_col = (jnp.concatenate(comp_cols, axis=-1)
                       if all(c is not None for c in comp_cols) else None)
    prefix = parts[0].path.rsplit(".", 1)[0] if "." in parts[0].path else ""
    base = parts[0]
    return QuantizedWeight(
        w=None,
        q=jnp.concatenate([p.q for p in parts], axis=-1),
        scale=jnp.concatenate(
            [_bcast_col(p.scale, lead, n) for p, n in zip(parts, ns)],
            axis=-1),
        zp=(jnp.concatenate(
            [_bcast_col(p.zp, lead, n) for p, n in zip(parts, ns)],
            axis=-1) if base.zp is not None else None),
        colsum=(jnp.concatenate([p.colsum for p in parts], axis=-1)
                if base.colsum is not None else None),
        act_scale=base.act_scale, act_zp=base.act_zp,
        dlut=base.dlut, dlut_bank=base.dlut_bank,
        comp_r=base.comp_r, comp_c=base.comp_c, comp_mu=base.comp_mu,
        comp_col=merged_comp_col, mode=base.mode,
        path=(prefix + "." if prefix else "") + name,
        per_channel=True, merged=True)


def fuse_projections(params, *, consume: bool = False):
    """Serving-time projection merging over the decoder units: attention
    wq|wk|wv -> wqkv and (GLU) mlp w_gate|w_up -> w_gateup, concatenated
    along the output axis.  At decode scale (M = B tokens) every qdot
    call pays fixed dispatch/gather-setup cost, so 7 calls per layer
    becoming 4 is a direct cut at the step-level floor; outputs are
    bit-identical per column (the merged wrapper carries each member's
    scale/zp/colsum on its own column block).  Groups that are not
    safely mergeable — un-prequantized weights, mixed-design plan layers
    whose members gather different tables, MoE expert stacks (their
    scan consumes separate operands) — are left untouched.  Apply AFTER
    the rest of the precomputation ladder (prequantize -> calibrate ->
    plan -> comp cols); launch/serve.py does this by default
    (--no-fuse-proj to A/B).  The merged wrappers are serving-form
    (no float master).

    ``consume=True`` frees each group's member weights once the merged
    copy exists, so only one group is ever resident twice; ``params``
    must not be used afterwards (serve.prepare_params)."""
    def merge(node, names, merged_name):
        parts = [node[k] for k in names]
        m = _merge_group(parts, merged_name)
        if m is None:
            return node
        if consume:
            for p in parts:
                for a in (p.w, p.q):
                    if a is not None:
                        a.delete()
        node = {k: v for k, v in node.items() if k not in names}
        node[merged_name] = m
        return node

    def visit(node):
        if isinstance(node, dict):
            node = {k: visit(v) for k, v in node.items()}
            if "router" in node:          # MoE dict: expert stacks stay
                return node
            if all(k in node for k in ("wq", "wk", "wv")):
                node = merge(node, ("wq", "wk", "wv"), "wqkv")
            if "w_gate" in node and "w_up" in node:
                node = merge(node, ("w_gate", "w_up"), "w_gateup")
            return node
        if isinstance(node, (list, tuple)):
            return type(node)(visit(v) for v in node)
        return node

    out = dict(params)
    out["units"] = visit(params["units"])
    return out


def qeinsum_heads(x: jax.Array, w: jax.Array, cfg: QuantConfig) -> jax.Array:
    """Batched per-head projection: x (..., K) @ w (H, K, D) -> (..., H, D).

    Implemented as a single qdot against w reshaped to (K, H*D) so the
    approximate product is applied uniformly.
    """
    H, K, D = w.shape
    y = qdot(x, w.transpose(1, 0, 2).reshape(K, H * D), cfg)
    return y.reshape(*x.shape[:-1], H, D)
