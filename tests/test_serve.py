"""Smoke tests for the serving driver (launch/serve.py): prefill + decode
loop on the smallest smoke config, exact + approximate, both quant modes."""
import numpy as np
import pytest

from repro.launch import serve

ARCH = "qwen3-1.7b"


def _run(**kw):
    args = ["--arch", ARCH, "--smoke", "--requests", "2",
            "--prompt-len", "3", "--gen-len", "4"]
    for k, v in kw.items():
        args += [f"--{k.replace('_', '-')}", str(v)]
    return serve.main(args)


@pytest.mark.parametrize("design,quant_mode", [
    ("exact", "asym_u8"),
    ("design2", "asym_u8"),
    ("design2", "sym_i8"),
])
def test_serve_smoke_loop(design, quant_mode):
    from repro import configs
    cfg = configs.get_smoke(ARCH)
    out, logits = _run(design=design, quant_mode=quant_mode)
    assert out.shape == (2, 4)  # (requests, gen_len) generated ids
    assert out.dtype == np.int32
    assert (out >= 0).all() and (out < cfg.vocab).all()
    assert logits.shape[0] == 2 and logits.shape[-1] == cfg.vocab
    assert np.isfinite(logits).all()


def test_serve_greedy_is_deterministic():
    out1, _ = _run(design="design2", quant_mode="sym_i8")
    out2, _ = _run(design="design2", quant_mode="sym_i8")
    np.testing.assert_array_equal(out1, out2)


@pytest.mark.parametrize("quant_mode", ["asym_u8", "sym_i8"])
def test_prequantized_weights_decode_speedup(quant_mode):
    """Weight prequantization (quant.prequantize_weights): identical
    greedy tokens and ULP-close logits (cached q/scale/zp are
    value-identical; only float-reduction fusion differs between the two
    graphs), a strictly smaller per-step graph (the weight
    min/max/round/clip ops disappear), and a measured decode-step
    speedup (printed; the wall-time assert is deliberately loose)."""
    import time

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models import transformer as T
    from repro.quant import QuantConfig, prequantize_weights
    from repro.train import make_serve_step

    cfg = configs.get_smoke(ARCH)
    qcfg = QuantConfig(design="design2", backend="xla", mode=quant_mode)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    pparams = prequantize_weights(params, qcfg)
    step = make_serve_step(cfg, qcfg)
    B, s_max, steps = 2, 12, 10
    tok0 = jnp.full((B, 1), 5, jnp.int32)

    def run(ps):
        st = T.init_decode_state(cfg, B, s_max)
        fn = jax.jit(step)
        tok, logits, st = fn(ps, st, tok0)          # compile + prefill 1
        toks = [np.asarray(tok)]
        t0 = time.perf_counter()
        for _ in range(steps):
            tok, logits, st = fn(ps, st, tok)
            toks.append(np.asarray(tok))
        jax.block_until_ready(logits)
        return np.concatenate(toks, 1), np.asarray(logits), \
            time.perf_counter() - t0

    toks_raw, logits_raw, t_raw = run(params)
    toks_pre, logits_pre, t_pre = run(pparams)

    # same greedy trajectory; logits agree to float-reduction ULPs
    np.testing.assert_array_equal(toks_raw, toks_pre)
    np.testing.assert_allclose(logits_raw, logits_pre, rtol=1e-4, atol=1e-5)

    # structural: the per-step jaxpr loses the weight-quantization ops
    st = T.init_decode_state(cfg, B, s_max)
    j_raw = str(jax.make_jaxpr(step)(params, st, tok0))
    j_pre = str(jax.make_jaxpr(step)(pparams, st, tok0))
    assert len(j_pre) < len(j_raw)

    print(f"[prequant {quant_mode}] decode {steps} steps: "
          f"raw {t_raw*1e3:.1f}ms, prequant {t_pre*1e3:.1f}ms "
          f"({t_raw/max(t_pre, 1e-9):.2f}x)")
    assert t_pre < t_raw * 1.5  # loose: CI noise must not flake this


def test_prepare_params_leaves_serving_form():
    """serve.prepare_params consumes the float tree (each master freed
    once quantized, merged members freed once merged) and leaves
    serving-form wrappers with no master; such a wrapper refuses a
    config it cannot serve instead of requantizing from a master."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models import transformer as T
    from repro.quant import QuantConfig, QuantizedWeight, qdot

    args = serve.parse_args(["--arch", ARCH, "--smoke", "--calibrate", "1",
                             "--requests", "2", "--prompt-len", "3"])
    cfg = configs.get_smoke(ARCH)
    qcfg = serve.quant_config(args)
    assert qcfg.backend == "fused" and qcfg.inference
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tree, _ = serve.prepare_params(params, cfg, qcfg, args)
    attn = params["units"][0]["attn"]
    assert attn["wq"].is_deleted() and attn["wo"].is_deleted()
    assert not params["embed"].is_deleted()       # not quantized: kept
    wrappers = [w for w in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, QuantizedWeight))
        if isinstance(w, QuantizedWeight)]
    assert wrappers and all(w.w is None for w in wrappers)
    assert {"wqkv", "wo"} <= set(tree["units"][0]["attn"])
    wo = jax.tree.map(lambda a: a[0], tree["units"][0]["attn"]["wo"])
    x = jnp.ones((2, wo.shape[0]), jnp.float32)
    qdot(x, wo, qcfg)                                  # serves
    with pytest.raises(ValueError, match="no master"):
        qdot(x, wo, QuantConfig(mode="sym_i8", inference=True))
    with pytest.raises(ValueError, match="no master"):
        qdot(x, wo, QuantConfig(mode=qcfg.mode))       # STE needs w
