"""Fused quantize->delta->dequant serving kernel (kernels.ops.fused_qdot
+ quant.linear backend='fused'): exhaustive-design bit-exactness against
the unfused pipeline across mode (asym_u8/sym_i8) x granularity
(per-tensor/per-channel) x plan/no-plan, through BOTH lowerings (the
Pallas kernel in interpret mode and the blocked-XLA twin), plus the
inference-mode STE skip and the platform choices (kernels.platform).

The exhaustive sweeps reuse the K=1 trick of tests/test_delta.py with
IDENTITY quantizers (sx=1, zx=0): the float operands quantize to
themselves, so the fused kernel's output IS the design's full 256x256
product table — integer-accumulator bit-exactness of quantize->dot+
delta->dequant in one assert (and the Pallas run exercises the
K-padding correction, since K=1 pads to a block).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut as lutmod
from repro.core.multipliers import MULTIPLIERS
from repro.kernels import ops, ref
from repro.kernels import platform
from repro.kernels.approx_matmul import delta_matmul
from repro.quant import QuantConfig, prequantize_weights, qdot
from repro.quant import linear as qlin
from repro.signed.multipliers import SIGNED_MULTIPLIERS

# ---------------------------------------------------------------------------
# Exhaustive per-design integer bit-exactness, both lowerings
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lowering", ["xla", "pallas"])
@pytest.mark.parametrize("name", sorted(MULTIPLIERS))
def test_fused_unsigned_exhaustive(name, lowering):
    x = jnp.arange(256, dtype=jnp.float32)[:, None]        # (256, 1)
    qw = jnp.arange(256, dtype=jnp.int32)[None, :]         # (1, 256)
    y = ops.fused_qdot(x, qw, jnp.asarray(ops.get_delta_lut(name)),
                       sx=1.0, zx=0.0, sw=1.0, zw=0.0,
                       colsum=np.zeros(256, np.float32),
                       signed=False, compensate=False, lowering=lowering)
    np.testing.assert_array_equal(
        np.asarray(y), lutmod.build_lut(name).astype(np.float32))


@pytest.mark.parametrize("lowering", ["xla", "pallas"])
@pytest.mark.parametrize("name", sorted(SIGNED_MULTIPLIERS))
def test_fused_signed_exhaustive(name, lowering):
    r = jnp.arange(-128, 128, dtype=jnp.int32)
    y = ops.fused_qdot(r[:, None].astype(jnp.float32), r[None, :],
                       jnp.asarray(ops.get_delta_lut(name, True)),
                       sx=1.0, sw=1.0, signed=True, compensate=False,
                       lowering=lowering)
    np.testing.assert_array_equal(
        np.asarray(y), lutmod.build_signed_lut(name).astype(np.float32))


@pytest.mark.parametrize("lowering", ["xla", "pallas"])
def test_fused_bank_index_selects_table(lowering):
    """A stacked table bank + dlut_idx gathers layer idx's table — the
    mixed-design plan path's kernel-operand contract."""
    designs = ["design1", "design2"]
    bank = jnp.asarray(np.stack(
        [np.asarray(ops.get_delta_lut(d)).astype(np.int32)
         for d in designs]))
    x = jnp.arange(256, dtype=jnp.float32)[:, None]
    qw = jnp.arange(256, dtype=jnp.int32)[None, :]
    for i, d in enumerate(designs):
        y = ops.fused_qdot(x, qw, bank, dlut_idx=jnp.int32(i),
                           sx=1.0, zx=0.0, sw=1.0, zw=0.0,
                           colsum=np.zeros(256, np.float32),
                           signed=False, compensate=False,
                           lowering=lowering)
        np.testing.assert_array_equal(
            np.asarray(y), lutmod.build_lut(d).astype(np.float32))


# ---------------------------------------------------------------------------
# The one-hot kernel (the TPU's lowering) against the twin and the gate level
# ---------------------------------------------------------------------------

ONEHOT_TABLES = ["design2", "design1", "dadda", "random", "bank0", "bank1",
                 "bank2"]
BANK = ("design1", "design2", "dadda")


def _onehot_table(name, signed, rng):
    """(dlut, dlut_idx, elementwise product of operand values) for a
    table of ONEHOT_TABLES: a registered design and its gate-level
    multiplier (the signed registry's 'exact' stands for dadda), a
    random int16 delta table over Design #2's delta range and its own
    products, or table i of a (3, 256, 256) bank of BANK."""
    reg = SIGNED_MULTIPLIERS if signed else MULTIPLIERS
    design = lambda d: "exact" if signed and d == "dadda" else d  # noqa
    if name.startswith("bank"):
        i = int(name[4:])
        bank = np.stack([np.asarray(ops.get_delta_lut(design(d), signed))
                         for d in BANK]).astype(np.int16)
        return jnp.asarray(bank), jnp.int32(i), reg[design(BANK[i])]
    if name != "random":
        return (jnp.asarray(ops.get_delta_lut(design(name), signed)), None,
                reg[design(name)])
    d2 = np.asarray(ops.get_delta_lut("design2", signed)).astype(np.int64)
    d = rng.integers(d2.min(), d2.max() + 1, (256, 256))
    off = 128 if signed else 0
    return (jnp.asarray(d.astype(np.int16)), None,
            lambda a, b: a * b + d[a + off, b + off])


@pytest.mark.parametrize("M", [1, 4, 16, 64])
@pytest.mark.parametrize("mode", ["asym_u8", "sym_i8"])
@pytest.mark.parametrize("table", ONEHOT_TABLES)
def test_onehot_kernel_bit_exact(table, mode, M):
    """The one-hot contraction kernel (lowering 'pallas', interpret mode
    here) gives the integer product sum_k P(qx, qw) bit for bit: equal
    to the gate-level multiplier's (or the table's own) and to the
    blocked-XLA twin's, for any table, bank entry, mode and M, at a K
    and an N that are no multiple of its blocks.  With real scales,
    zero points and per-channel columns the two lowerings' outputs are
    equal without compensation and ULP-close with it (the row table's
    sum is a histogram dot on the kernel's side)."""
    signed = mode == "sym_i8"
    rng = np.random.default_rng(M)
    K, N = 131, 200
    dlut, idx, product = _onehot_table(table, signed, rng)
    lo, hi = (-128, 127) if signed else (0, 255)
    qx = rng.integers(lo, hi + 1, (M, K))
    qw = rng.integers(lo, hi + 1, (K, N))
    want = product(qx[:, :, None], qw[None]).sum(1)
    ident = dict(dlut_idx=idx, sx=1.0, sw=1.0, signed=signed,
                 zx=None if signed else 0.0, zw=None if signed else 0.0)
    xq, qwj = jnp.asarray(qx, jnp.float32), jnp.asarray(qw, jnp.int32)
    got = {low: np.asarray(ops.fused_qdot(xq, qwj, dlut, lowering=low,
                                          **ident))
           for low in ("pallas", "xla")}
    np.testing.assert_array_equal(got["pallas"], want.astype(np.float32))
    np.testing.assert_array_equal(got["xla"], got["pallas"])

    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    kw = dict(dlut_idx=idx, signed=signed,
              sw=jnp.asarray(rng.uniform(0.01, 0.02, (1, N)), jnp.float32))
    if signed:
        kw.update(sx=float(np.abs(x).max()) / 127.0)
    else:
        sx = float(x.max() - x.min()) / 255.0
        kw.update(sx=sx, zx=float(np.round(-float(x.min()) / sx)),
                  zw=jnp.asarray(rng.integers(100, 156, (1, N)), jnp.float32),
                  colsum=qwj.sum(0).astype(jnp.float32))
    mu_r, mu_c, mu = qlin._mean_field_tables("design2", signed=signed)
    off = 128 if signed else 0
    for compensate in (False, True):
        if compensate:
            kw.update(comp_r=mu_r, comp_mu=mu,
                      comp_col=jnp.take(mu_c, qwj + off, axis=0).sum(0))
        y = {low: np.asarray(ops.fused_qdot(x, qwj, dlut, lowering=low,
                                            compensate=compensate, **kw))
             for low in ("pallas", "xla")}
        if compensate:
            np.testing.assert_allclose(
                y["pallas"], y["xla"], rtol=2e-6,
                atol=2e-6 * max(np.abs(y["xla"]).max(), 1.0))
        else:
            np.testing.assert_array_equal(y["pallas"], y["xla"])


# ---------------------------------------------------------------------------
# Fused vs unfused through qdot: mode x granularity x plan/no-plan
# ---------------------------------------------------------------------------

SHAPES = [(5, 100, 70), (4, 64, 192), (1, 300, 33)]


def _static_wrap(x, w, cfg):
    """Prequantize + hand-install static activation scales computed the
    calibration way (min/max or absmax of the calibration data == x)."""
    tree = prequantize_weights({"w": w}, cfg)
    pre = tree["w"]
    xnp = np.asarray(x)
    if cfg.signed:
        s = max(float(np.abs(xnp).max()) / 127.0, 1e-8)
        return pre.replace(act_scale=jnp.float32(s))
    lo, hi = float(xnp.min()), float(xnp.max())
    s = max((hi - lo) / 255.0, 1e-8)
    zp = float(np.clip(np.round(-lo / s), 0, 255))
    return pre.replace(act_scale=jnp.float32(s), act_zp=jnp.float32(zp))


def _plan_wrap(pre, mode, designs=("design1",)):
    """Install a per-layer table bank on a 2-D (single-layer) wrapper."""
    from repro.calib import DesignPlan
    from repro.calib.plan import apply_plan
    plan = DesignPlan(arch="t", mode=mode, default=designs[0],
                      layers={pre.path: designs[0]})
    return apply_plan({pre.path: pre}, plan, QuantConfig(mode=mode))[pre.path]


@pytest.mark.parametrize("mode", ["asym_u8", "sym_i8"])
@pytest.mark.parametrize("per_channel", [False, True])
@pytest.mark.parametrize("plan", [False, True])
def test_fused_matches_unfused_pipeline(mode, per_channel, plan):
    rng = np.random.default_rng(7)
    for M, K, N in SHAPES:
        x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
        base = QuantConfig(design="design1", backend="delta_xla", mode=mode,
                           w_per_channel=per_channel, inference=True,
                           compensate=False)
        pre = _static_wrap(x, w, base)
        if plan:
            pre = _plan_wrap(pre, mode)
        for compensate in (False, True):
            cfg_u = dataclasses.replace(base, compensate=compensate)
            cfg_f = dataclasses.replace(cfg_u, backend="fused")
            y_u = np.asarray(qdot(x, pre, cfg_u))
            y_f = np.asarray(qdot(x, pre, cfg_f))
            if compensate:
                # the fused row-compensation sum reassociates; integer
                # core identical, float epilogue ULP-close
                np.testing.assert_allclose(
                    y_f, y_u, rtol=2e-6,
                    atol=2e-6 * max(np.abs(y_u).max(), 1.0))
            else:
                # identical float op sequence end to end -> bit-equal
                np.testing.assert_array_equal(y_f, y_u)


@pytest.mark.parametrize("mode", ["asym_u8", "sym_i8"])
@pytest.mark.parametrize("per_channel", [False, True])
@pytest.mark.parametrize("compensate", [False, True])
def test_fused_lowerings_agree(mode, per_channel, compensate):
    """The Pallas fused kernel (interpret off-TPU) agrees with the XLA
    twin on the FULL epilogue — nonzero zero points, per-channel
    scales, compensation tables, K-padding corrections (odd shape) —
    not just the zeroed-out exhaustive sweeps above."""
    rng = np.random.default_rng(13)
    M, K, N = 5, 100, 70
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    cfg = QuantConfig(design="design2", backend="fused", mode=mode,
                      w_per_channel=per_channel, inference=True,
                      compensate=compensate)
    pre = _static_wrap(x, w, cfg)
    signed = cfg.signed
    off = 128 if signed else 0
    kw = dict(
        sx=pre.act_scale,
        zx=pre.act_zp,
        sw=pre.scale, zw=pre.zp,
        colsum=(pre.colsum.reshape(-1) if pre.colsum is not None else None),
        signed=signed, compensate=compensate)
    if compensate:
        mu_r, mu_c, mu = qlin._mean_field_tables(cfg.design, signed=signed)
        kw.update(comp_r=mu_r, comp_mu=mu,
                  comp_col=jnp.take(mu_c, pre.q + off, axis=0).sum(0))
    dlut = jnp.asarray(ops.get_delta_lut(cfg.design, signed))
    y_xla = np.asarray(ops.fused_qdot(x, pre.q, dlut, lowering="xla", **kw))
    y_pal = np.asarray(ops.fused_qdot(x, pre.q, dlut, lowering="pallas",
                                      **kw))
    # the Pallas row-compensation/rowsum accumulate blockwise (float
    # reassociation); everything else is op-for-op identical
    np.testing.assert_allclose(y_pal, y_xla, rtol=2e-6,
                               atol=2e-6 * max(np.abs(y_xla).max(), 1.0))


def test_fused_requires_static_scales():
    """backend='fused' without calibrated act scales falls back to the
    unfused pipeline (whose product backend aliases 'fused' to
    'delta') instead of failing."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    cfg_f = QuantConfig(design="design2", backend="fused", inference=True)
    cfg_d = dataclasses.replace(cfg_f, backend="delta")
    np.testing.assert_array_equal(np.asarray(qdot(x, w, cfg_f)),
                                  np.asarray(qdot(x, w, cfg_d)))


@pytest.mark.parametrize("mode", ["asym_u8", "sym_i8"])
def test_attach_comp_cols_matches_per_call_gather(mode):
    """The compensation colsum cached by calib.static.attach_comp_cols
    equals the fused path's per-call fallback gather, and the fused
    outputs agree with and without the cache."""
    from repro.calib import attach_comp_cols

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 40)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(40, 24)).astype(np.float32))
    cfg = QuantConfig(design="design2", backend="fused", mode=mode,
                      inference=True)
    pre = _static_wrap(x, w, cfg)
    tree = attach_comp_cols({"w": pre}, cfg)
    cached = tree["w"]
    assert cached.comp_col is not None
    assert cached.comp_col.shape == (1, 24)
    _, mu_c, _ = qlin._mean_field_tables(cfg.design, signed=cfg.signed)
    off = 128 if cfg.signed else 0
    want = np.asarray(jnp.take(mu_c, pre.q + off, axis=0).sum(0))
    np.testing.assert_allclose(np.asarray(cached.comp_col).reshape(-1),
                               want, rtol=1e-5, atol=1e-5)
    y_cached = np.asarray(qdot(x, cached, cfg))
    y_fallback = np.asarray(qdot(x, pre, cfg))
    np.testing.assert_allclose(y_cached, y_fallback, rtol=1e-6,
                               atol=1e-6 * np.abs(y_fallback).max())
    # plan-installed wrappers (comp_c present) are left untouched
    planned = _plan_wrap(pre, mode)
    tree2 = attach_comp_cols({"w": planned}, cfg)
    np.testing.assert_array_equal(np.asarray(tree2["w"].comp_col),
                                  np.asarray(planned.comp_col))


def test_banked_plan_matches_legacy_table_wrapper():
    """The bank-index plan form (apply_plan) and a legacy table-carrying
    wrapper produce identical unfused AND fused outputs."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 48)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(48, 24)).astype(np.float32))
    cfg = QuantConfig(design="design2", backend="delta_xla", mode="sym_i8",
                      inference=True)
    pre = _static_wrap(x, w, cfg)
    banked = _plan_wrap(pre, "sym_i8", designs=("design1",))
    legacy = pre.replace(
        dlut=jnp.asarray(ops.get_delta_lut("design1", True)))
    for backend in ("delta_xla", "fused"):
        c = dataclasses.replace(cfg, backend=backend)
        np.testing.assert_array_equal(np.asarray(qdot(x, banked, c)),
                                      np.asarray(qdot(x, legacy, c)))


# ---------------------------------------------------------------------------
# Inference-mode STE skip
# ---------------------------------------------------------------------------

def test_inference_skips_ste_matmul():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    cfg = QuantConfig(design="design2", backend="delta_xla", mode="sym_i8")
    cfg_inf = dataclasses.replace(cfg, inference=True)
    y = np.asarray(qdot(x, w, cfg))
    y_inf = np.asarray(qdot(x, w, cfg_inf))
    # numerically the STE expression evaluates to y: only float
    # reassociation ULPs may differ
    np.testing.assert_allclose(y_inf, y, rtol=1e-6,
                               atol=1e-6 * np.abs(y).max())
    # structurally: the exact fp matmul disappears (count dot_generals)
    n_dots = str(jax.make_jaxpr(
        lambda x, w: qdot(x, w, cfg))(x, w)).count("dot_general")
    n_dots_inf = str(jax.make_jaxpr(
        lambda x, w: qdot(x, w, cfg_inf))(x, w)).count("dot_general")
    assert n_dots_inf < n_dots


def test_inference_default_off_keeps_gradients():
    cfg = QuantConfig(design="design2", backend="delta_xla", mode="sym_i8")
    assert not cfg.inference
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    g = jax.grad(lambda w: qdot(x, w, cfg).sum())(w)
    # STE: gradient of the exact product
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(jax.grad(
                                   lambda w: jnp.matmul(x, w).sum())(w)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Platform choices (kernels.platform) + K-subtile gather
# ---------------------------------------------------------------------------

def test_platform_lowering_choice(monkeypatch):
    """Interpret mode only on the CPU; on the TPU the qdot runs the
    one-hot kernel, decode attention the Pallas kernel, and an explicit
    request for a kernel the TPU compiler refuses raises (never
    interprets, never swaps lowerings).  Delta tables follow the qdot
    lowering."""
    monkeypatch.setattr(platform, "backend", lambda: "cpu")
    assert platform.pallas_interpret("delta_matmul") is True
    assert platform.lowering("qdot") == "xla"
    assert platform.lowering("decode_attention") == "xla"
    assert platform.lowering("qdot", "pallas") == "pallas"
    assert platform.delta_table_dtype() == jnp.int32
    assert platform.donate(1) == ()
    monkeypatch.setattr(platform, "backend", lambda: "tpu")
    assert platform.lowering("qdot") == "pallas"
    assert platform.lowering("qdot", "xla") == "xla"
    assert platform.lowering("decode_attention") == "pallas"
    assert platform.pallas_interpret("onehot_qdot") is False
    assert platform.pallas_interpret("decode_attention_step") is False
    assert platform.delta_table_dtype() is None
    assert platform.donate(1) == (1,)
    with pytest.raises(NotImplementedError, match="Mosaic"):
        platform.pallas_interpret("delta_matmul")
    # shapes no other test traces: the jitted kernel must trace anew
    a = jnp.ones((3, 136), jnp.int32)
    b = jnp.ones((136, 40), jnp.int32)
    with pytest.raises(NotImplementedError, match="delta_matmul"):
        ops.approx_matmul(a, b, "design2", "pallas")
    with pytest.raises(ValueError):
        platform.lowering("qdot", "interpret")
    monkeypatch.setattr(platform, "backend", lambda: "gpu")
    with pytest.raises(NotImplementedError):
        platform.pallas_interpret("decode_attention_step")


@pytest.mark.parametrize("k_sub", [8, 32, 128, 999])
def test_delta_matmul_k_sub_sweep(k_sub):
    """The K-subtiled stage-2 gather is bit-exact for any k_sub
    (non-divisors round down to a divisor of TK)."""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.integers(0, 256, (130, 200)).astype(np.int32))
    b = jnp.asarray(rng.integers(0, 256, (200, 70)).astype(np.int32))
    want = ref.approx_matmul_ref(a, b, ops.get_lut("design2"))
    got = delta_matmul(a, b, jnp.asarray(ops.get_delta_lut("design2")),
                       k_sub=k_sub)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dlut_bank_registry_errors():
    with pytest.raises(KeyError):
        qlin.get_dlut_bank("no-such-bank")
