"""Fused full-sequence prefill: bit-equivalence with the token-by-token
decode loop (tokens, logits, and the post-prefill decode state) across
quant mode x static/dynamic activation scales x plan/no-plan, through
the serving tree launch/serve.py actually builds (merged projections,
comp colsums).  Plus the continuous-batching driver smoke."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.calib import (apply_calibration, apply_plan, attach_comp_cols,
                         calibrate_decode, plan_designs)
from repro.models import transformer as T
from repro.quant import QuantConfig, fuse_projections, prequantize_weights
from repro.train import make_prefill_step, make_serve_step

ARCH = "qwen3-1.7b"
B, P, GEN = 2, 5, 3


def _trees(mode: str, prep: str):
    """Build (tree, serving_qcfg) the way launch/serve.py would."""
    cfg = configs.get_smoke(ARCH)
    qcfg = QuantConfig(design="design2", backend="xla", mode=mode)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    if prep == "dynamic":
        return cfg, params, dataclasses.replace(qcfg, inference=True)
    pp = prequantize_weights(params, qcfg)
    if prep == "prequant":
        return cfg, pp, dataclasses.replace(qcfg, inference=True)
    cal = np.random.default_rng(7).integers(
        0, cfg.vocab, (B, 4)).astype(np.int32)
    table = calibrate_decode(pp, cfg, qcfg, cal, gen_len=2)
    sp = apply_calibration(pp, table)
    qf = dataclasses.replace(qcfg, backend="fused", inference=True)
    if prep == "static":
        return cfg, fuse_projections(attach_comp_cols(sp, qf)), qf
    assert prep == "static_plan"
    plan = plan_designs(table, qcfg, arch=ARCH)
    mp = apply_plan(attach_comp_cols(sp, qf), plan, qf)
    return cfg, fuse_projections(mp), qf


@pytest.mark.parametrize("mode", ["asym_u8", "sym_i8"])
@pytest.mark.parametrize("prep", ["dynamic", "prequant", "static",
                                  "static_plan"])
def test_prefill_bit_identical_to_token_loop(mode, prep):
    """The full-sequence prefill pass must hand off the state the token
    loop would have produced: prompt logits (to f32 reassociation ULPs)
    — and the greedy continuation decoded from it must match token for
    token.  The cache entries are compared bit for bit by
    test_prefill_state_handoff_bitwise."""
    cfg, tree, qcfg = _trees(mode, prep)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (B, P)).astype(np.int32)
    s_max = P + GEN + 1
    step = jax.jit(make_serve_step(cfg, qcfg))
    prefill = jax.jit(make_prefill_step(cfg, qcfg))

    # token-by-token baseline
    st = T.init_decode_state(cfg, B, s_max)
    logits_loop = []
    for i in range(P):
        tok_l, lg, st = step(tree, st, jnp.asarray(prompts[:, i:i + 1]))
        logits_loop.append(np.asarray(lg))
    logits_loop = np.concatenate(logits_loop, axis=1)
    gen_loop = [np.asarray(tok_l)]
    for _ in range(GEN - 1):
        tok_l, lg, st = step(tree, st, tok_l)
        gen_loop.append(np.asarray(tok_l))

    # fused full-sequence prefill + the same decode loop
    st2 = T.init_decode_state(cfg, B, s_max)
    tok_p, logits_pf, st2 = prefill(tree, st2, jnp.asarray(prompts))
    gen_pf = [np.asarray(tok_p)]
    for _ in range(GEN - 1):
        tok_p, lg2, st2 = step(tree, st2, tok_p)
        gen_pf.append(np.asarray(tok_p))

    # The integer core is exact by construction, and the tokens must
    # match exactly.  The float logits come out of two different XLA
    # programs (an M = B·P pass against M = B steps), and XLA may fuse
    # and reassociate the float epilogues and reductions differently
    # in each: they agree to f32 reassociation ULPs of the logit scale,
    # not bit for bit.
    np.testing.assert_allclose(
        np.asarray(logits_pf), logits_loop, rtol=0,
        atol=8 * np.finfo(np.float32).eps * np.abs(logits_loop).max())
    np.testing.assert_array_equal(np.concatenate(gen_loop, 1),
                                  np.concatenate(gen_pf, 1))


@pytest.mark.parametrize("mode", ["asym_u8", "sym_i8"])
def test_prefill_state_handoff_bitwise(mode):
    """Every leaf of the post-prefill decode state (K/V caches, idx)
    equals the token-loop state bit for bit, static AND dynamic."""
    for prep in ("dynamic", "static"):
        cfg, tree, qcfg = _trees(mode, prep)
        prompts = np.random.default_rng(1).integers(
            0, cfg.vocab, (B, P)).astype(np.int32)
        step = jax.jit(make_serve_step(cfg, qcfg))
        prefill = jax.jit(make_prefill_step(cfg, qcfg))
        st = T.init_decode_state(cfg, B, P + 2)
        for i in range(P):
            _, _, st = step(tree, st, jnp.asarray(prompts[:, i:i + 1]))
        st2 = T.init_decode_state(cfg, B, P + 2)
        _, _, st2 = prefill(tree, st2, jnp.asarray(prompts))
        for a, b in zip(jax.tree.leaves(st["caches"]),
                        jax.tree.leaves(st2["caches"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{prep}/{mode}")


def test_merged_projections_bit_identical():
    """fuse_projections (wqkv / w_gateup) changes nothing numerically:
    the merged tree's decode step and prefill equal the unmerged
    tree's, bitwise, for both quant modes."""
    for mode in ("asym_u8", "sym_i8"):
        cfg = configs.get_smoke(ARCH)
        qcfg = QuantConfig(design="design2", backend="xla", mode=mode)
        qf = dataclasses.replace(qcfg, backend="fused", inference=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        pp = prequantize_weights(params, qcfg)
        cal = np.random.default_rng(7).integers(
            0, cfg.vocab, (B, 4)).astype(np.int32)
        table = calibrate_decode(pp, cfg, qcfg, cal, gen_len=2)
        sp = attach_comp_cols(apply_calibration(pp, table), qf)
        sm = fuse_projections(sp)
        # merged wrappers exist and carry per-column scales
        unit0 = sm["units"][0]
        assert "wqkv" in unit0["attn"] and "wq" not in unit0["attn"]
        assert "w_gateup" in unit0["mlp"]
        prompts = np.random.default_rng(3).integers(
            0, cfg.vocab, (B, P)).astype(np.int32)
        prefill = jax.jit(make_prefill_step(cfg, qf))
        st1 = T.init_decode_state(cfg, B, P + 1)
        st2 = T.init_decode_state(cfg, B, P + 1)
        _, lg_u, _ = prefill(sp, st1, jnp.asarray(prompts))
        _, lg_m, _ = prefill(sm, st2, jnp.asarray(prompts))
        np.testing.assert_array_equal(np.asarray(lg_u), np.asarray(lg_m))


def test_serve_prefill_modes_agree_e2e():
    """launch/serve.py --prefill fused vs --prefill loop produce the
    same generated ids end to end (calibrated fused serving tree)."""
    from repro.launch import serve
    base = ["--arch", ARCH, "--smoke", "--requests", "2",
            "--prompt-len", "3", "--gen-len", "4", "--calibrate", "1"]
    out_f, _ = serve.main(base + ["--prefill", "fused"])
    out_l, _ = serve.main(base + ["--prefill", "loop"])
    np.testing.assert_array_equal(out_f, out_l)


def test_serve_continuous_matches_isolated_requests():
    """Continuous batching (per-slot cache positions, slot reuse) must
    serve each queued request exactly as a fresh batch run would under
    static scales: no cross-slot contamination, no stale-cache reads
    after a slot is re-prefilled."""
    from repro.launch import serve
    args = ["--arch", ARCH, "--smoke", "--requests", "2",
            "--prompt-len", "4", "--gen-len", "5", "--calibrate", "1"]
    out_c, _ = serve.main(args + ["--continuous", "5"])
    assert out_c.shape == (5, 5)
    # replay request r alone through the standard batched path, on the
    # EXACT tree the driver served (prepare_params is deterministic —
    # calibration uses its own rng) and the same prompt stream (the
    # continuous driver draws prompts from rng(0) as (N, P))
    import argparse
    cfg = configs.get_smoke(ARCH)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (5, 4)).astype(np.int32)
    qcfg = QuantConfig(design="design2", backend="fused",
                       mode="asym_u8", inference=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    ns = argparse.Namespace(prequantize=False, calibrate=1, plan=None,
                            clip="minmax", no_fuse_proj=False,
                            requests=2, prompt_len=4)
    tree, _ = serve.prepare_params(params, cfg, qcfg, ns)
    step = jax.jit(make_serve_step(cfg, qcfg))
    prefill = jax.jit(make_prefill_step(cfg, qcfg))
    for r in range(5):
        st = T.init_decode_state(cfg, 1, 4 + 2 * 5 + 2, per_slot=True)
        tok, _, st = prefill(tree, st, jnp.asarray(prompts[r:r + 1]))
        got = [int(np.asarray(tok)[0, 0])]
        for _ in range(4):
            tok, _, st = step(tree, st, tok)
            got.append(int(np.asarray(tok)[0, 0]))
        np.testing.assert_array_equal(out_c[r], got, err_msg=f"req {r}")


def test_act_per_pos_noop_on_static_and_single_token():
    """act_per_pos only changes DYNAMIC multi-position quantization:
    at S = 1 it reduces over the same block as the default."""
    cfg, tree, qcfg = _trees("asym_u8", "dynamic")
    qpp = dataclasses.replace(qcfg, act_per_pos=True)
    tok = jnp.full((B, 1), 3, jnp.int32)
    st1 = T.init_decode_state(cfg, B, 4)
    st2 = T.init_decode_state(cfg, B, 4)
    lg1, _ = T.forward_decode(tree, st1, tok, cfg, qcfg)
    lg2, _ = T.forward_decode(tree, st2, tok, cfg, qpp)
    np.testing.assert_array_equal(np.asarray(lg1), np.asarray(lg2))
