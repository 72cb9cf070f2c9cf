"""Batched-request serving driver: fused full-sequence prefill + batched
decode loop with a KV/state cache, greedy sampling, and continuous-
batching slot reuse.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --requests 4 --gen-len 16

Prefill runs the WHOLE prompt as one M = B·S pass through the decode
stack (train.make_prefill_step): causal attention over the fresh KV
block, cache written in one slice, and a decode handoff bit-identical
to stepping the prompt token by token (--prefill loop keeps the old
per-token path for A/B).  The decode step's attention/rope/cache-append
runs through the fused decode-attention op (kernels.ops.decode_attention
— its attention is a Pallas kernel on the TPU, the bit-matched XLA twin
elsewhere; kernels.platform decides each op's lowering).

Quantization precomputation ladder (see quant/linear.py):
  --prequantize      cache weight quantization once (q/scale/zp/colsum)
  --per-channel      per-output-channel weight scales
  --calibrate N      run N calibration batches through the decode path
                     and fix STATIC per-layer activation scales (drops
                     the per-token min/max reduction from the step)
  --clip MODE        activation-range calibrator: minmax (default) |
                     pct999 (99.9th percentile) | mse (MSE-optimal),
                     selected from the calibration histograms
  --plan FILE        load a DesignPlan (repro.calib.plan / scripts/
                     make_plan.sh) and serve a per-layer MIXED-design
                     decode: each scanned layer gathers its own
                     design's delta table
--calibrate and --plan imply --prequantize (the caches they attach to).

With static scales installed (--calibrate / --plan) the backend
defaults to 'fused' (else 'delta'): one lowered body quantizes the
activations, runs the two-stage exact-dot + delta-gather (the plan's
per-layer tables ride the scan as operands) and dequantizes in the
epilogue, and the
attention wq|wk|wv / mlp gate|up projections are MERGED into single
calls (quant.fuse_projections — bit-identical per column; disable with
--no-fuse-proj to A/B).  Pass an explicit --backend to A/B the unfused
pipeline.  Serving always runs qdot in inference mode (the exact STE
matmul — a training-only gradient vehicle that never changes the
output — is skipped).

--continuous N serves N total requests through the --requests slots
with per-slot cache positions (batched multi-slot decode): a slot that
finishes its generation is immediately re-prefilled with the next
queued request while the other slots keep decoding.

Timing is steady-state: both steps are AOT-compiled up front and the
compile time is reported separately (it used to be silently folded
into the first-call tok/s).  Every time printed is an obs span (compile,
prefill, decode; prepare_params and its stages in set-up), and the run
ends with the obs counters (traces of each step function).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.kernels import platform
from repro.models import transformer as T
from repro.quant import QuantConfig
from repro.train import make_prefill_step, make_serve_step


def _calibration_prompts(cfg, rng, batches: int, requests: int,
                         prompt_len: int):
    return [rng.integers(0, cfg.vocab, (requests, prompt_len))
            .astype(np.int32) for _ in range(batches)]


def prepare_params(params, cfg, qcfg, args):
    """Apply the requested precomputation ladder to a params tree.
    Returns (params, notes) — notes says what was installed.

    When any rung is requested, ``params`` is CONSUMED: each float
    weight is freed as soon as its quantized form exists, and merged
    projections free their members, so the prepare sequence never holds
    two copies of the layer weights (at qwen3-1.7b widths the float
    masters alone are 5.6 GB).  The serving tree keeps no master.

    Calibration draws from its OWN rng so enabling --calibrate never
    shifts the serving-prompt stream (A/B runs with and without it see
    identical requests).

    Each rung is an obs span under obs.PREPARE_PARAMS (prequantize,
    calibrate > calibrate_batch + apply_calibration, attach_comp_cols,
    fuse_projections), closed once its device work is done."""
    from repro.quant import fuse_projections, prequantize_weights
    notes = []
    wrap = args.prequantize or args.calibrate or args.plan
    if not wrap:
        return params, notes
    with obs.span(obs.PREPARE_PARAMS):
        with obs.span(obs.PREQUANTIZE):
            params = jax.block_until_ready(
                prequantize_weights(params, qcfg, consume=True))
        notes.append("prequantized weights"
                     + (" (per-channel)" if qcfg.w_per_channel else ""))
        if args.calibrate:
            with obs.span(obs.CALIBRATE):
                params, note = _calibrate(params, cfg, qcfg, args)
            notes.append(note)
        if args.plan:
            from repro.calib import DesignPlan, apply_plan
            plan = DesignPlan.load(args.plan)
            params = apply_plan(params, plan, qcfg)
            notes.append(f"design plan {args.plan} "
                         f"(histogram {plan.histogram()})")
        if qcfg.backend == "fused" and qcfg.compensate:
            # after apply_plan: plan-installed wrappers already carry
            # their per-layer comp_col and are skipped (comp_c present)
            from repro.calib import attach_comp_cols
            with obs.span(obs.ATTACH_COMP_COLS):
                params = jax.block_until_ready(
                    attach_comp_cols(params, qcfg))
            notes.append("fused backend (cached compensation colsums)")
        if not args.no_fuse_proj:
            with obs.span(obs.FUSE_PROJECTIONS):
                params = jax.block_until_ready(
                    fuse_projections(params, consume=True))
            notes.append("merged wq|wk|wv -> wqkv, w_gate|w_up -> w_gateup "
                         "(fuse_projections)")
    return params, notes


def _calibrate(params, cfg, qcfg, args):
    """Static activation scales from --calibrate batches (one
    calibrate_batch span each) -> (params, note)."""
    from repro.calib import apply_calibration, calibrate_decode
    crng = np.random.default_rng(4242)
    enc_frontend = None
    if cfg.family == "encdec":
        enc_frontend = crng.normal(size=(
            args.requests, 16,
            cfg.frontend_dim or cfg.d_model)).astype(np.float32)
    table = None
    for prompts in _calibration_prompts(cfg, crng, args.calibrate,
                                        args.requests, args.prompt_len):
        with obs.span(obs.CALIBRATE_BATCH):
            t = calibrate_decode(params, cfg, qcfg, prompts, gen_len=2,
                                 enc_frontend=enc_frontend)
        table = t if table is None else table.merge(t)
    with obs.span(obs.APPLY_CALIBRATION):
        params = jax.block_until_ready(
            apply_calibration(params, table, clip=args.clip))
    return params, (f"static act scales ({len(table.sites)} sites, "
                    f"{args.calibrate} calib batches, clip={args.clip})")


def _scatter_slot(state, one, slot: int):
    """Write a freshly-prefilled single-slot state into batched ``state``
    at ``slot`` (cache leaves are stacked (n_units, B, ...))."""
    def put(full, new):
        return full.at[:, slot].set(new[:, 0])
    caches = [jax.tree.map(put, c_full, c_one)
              for c_full, c_one in zip(state["caches"], one["caches"])]
    return dict(state, caches=caches)


def serve_continuous(params, cfg, qcfg, args, rng):
    """Continuous batching: --continuous N requests through --requests
    slots.  Per-slot cache positions (init_decode_state per_slot=True)
    let every slot sit at its own depth; a finished slot is immediately
    re-prefilled with the next queued request while the rest decode."""
    if cfg.family == "encdec":
        raise NotImplementedError("--continuous: encdec requests carry "
                                  "per-request encoder state")
    P, G = args.prompt_len, args.gen_len
    N = args.continuous
    B = min(args.requests, N)
    prompts = rng.integers(0, cfg.vocab, (N, P)).astype(np.int32)
    s_max = P + 2 * G + 2          # slack: idle slots keep stepping
    prefill = jax.jit(make_prefill_step(cfg, qcfg))
    prefill1 = jax.jit(make_prefill_step(cfg, qcfg))   # B=1 refill
    serve = jax.jit(make_serve_step(cfg, qcfg))

    # compile + warm up all three steps before the timed serve (same
    # steady-state policy as the main path; compile gets its own line)
    with obs.span(obs.COMPILE) as sp:
        warm = T.init_decode_state(cfg, B, s_max, per_slot=True)
        tok_w, _, warm = prefill(params, warm, jnp.asarray(prompts[:B]))
        jax.block_until_ready(serve(params, warm, tok_w)[0])
        warm1 = T.init_decode_state(cfg, 1, s_max, per_slot=True)
        jax.block_until_ready(
            prefill1(params, warm1, jnp.asarray(prompts[:1]))[0])
        del warm, warm1
    print(f"[serve] compile+warmup: {sp.seconds:.2f}s "
          f"(reported separately)")

    # prefill and decode each time their own spans, closed once the
    # tokens are ready (the host reads every step's tokens anyway)
    t_prefill = t_decode = 0.0
    with obs.span(obs.PREFILL) as sp:
        state = T.init_decode_state(cfg, B, s_max, per_slot=True)
        tok, logits, state = prefill(params, state,
                                     jnp.asarray(prompts[:B]))
        jax.block_until_ready(tok)
    t_prefill += sp.seconds
    slot_req = list(range(B))                 # request id per slot
    produced = {r: [] for r in range(B)}
    next_req = B
    steps = 0
    while any(r is not None for r in slot_req):
        # harvest the slots' current tokens, refilling finished slots
        # (the refill's own prefill token is recorded here — the next
        # batched step consumes it to produce the slot's second token)
        toks = np.asarray(tok)
        for slot, r in enumerate(slot_req):
            if r is None:
                continue
            produced[r].append(int(toks[slot, 0]))
            while slot_req[slot] is not None and \
                    len(produced[slot_req[slot]]) >= G:
                if next_req < N:          # slot reuse: prefill the next
                    with obs.span(obs.PREFILL) as sp:
                        st1 = T.init_decode_state(cfg, 1, s_max,
                                                  per_slot=True)
                        t1, _, st1 = prefill1(
                            params, st1,
                            jnp.asarray(prompts[next_req:next_req + 1]))
                        state = _scatter_slot(state, st1, slot)
                        tok = tok.at[slot].set(t1[0])
                        first = int(np.asarray(t1)[0, 0])
                    t_prefill += sp.seconds
                    slot_req[slot] = next_req
                    produced[next_req] = [first]
                    next_req += 1
                else:
                    slot_req[slot] = None
        if all(r is None for r in slot_req):
            break
        with obs.span(obs.DECODE) as sp:
            tok, logits, state = serve(params, state, tok)
            jax.block_until_ready(tok)
        t_decode += sp.seconds
        steps += 1
    out = np.asarray([produced[r] for r in range(N)], np.int32)
    # each request's first token comes from its prefill, the other
    # G - 1 from decode steps
    n_pre, n_dec = N * P, N * (G - 1)
    print(f"[serve] continuous: {N} requests over {B} slots, "
          f"{steps} batched decode steps")
    print(f"[serve] prefill: {n_pre} prompt tokens in "
          f"{t_prefill * 1e3:.1f}ms ({n_pre / t_prefill:.1f} tok/s)")
    print(f"[serve] decode: {n_dec} tokens in {t_decode * 1e3:.1f}ms "
          f"({n_dec / max(t_decode, 1e-9):.1f} tok/s, "
          f"{t_decode * 1e6 / max(steps, 1):.1f} us/step)")
    print("[serve] sample output ids:", out[0][:12].tolist())
    _print_counters()
    return out, np.asarray(logits)


def _print_counters() -> None:
    """The run's counters (obs): e.g. traces of each step function, so a
    recompile shows."""
    print("[serve] counters:", " ".join(
        f"{k}={v}" for k, v in sorted(obs.counters().items())))


def parse_args(argv=None) -> argparse.Namespace:
    """The serving CLI's options (``main`` and chip_smoke.py read them
    the same way)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--design", default="design2")
    ap.add_argument("--backend", default=None,
                    help="approximate-matmul backend (quant.QuantConfig)."
                         "  Default: 'fused' when static act scales are "
                         "installed (--calibrate/--plan), else 'delta'")
    ap.add_argument("--quant-mode", default="asym_u8",
                    choices=["asym_u8", "sym_i8"],
                    help="asym_u8: unsigned multiplier + zero-point "
                         "decomposition; sym_i8: symmetric int8 through "
                         "the signed multiplier subsystem")
    ap.add_argument("--prequantize", action="store_true",
                    help="quantize the (static) weights once up front "
                         "instead of per decode step (identical quantized "
                         "values; see quant.prequantize_weights)")
    ap.add_argument("--per-channel", action="store_true",
                    help="per-output-channel weight scales")
    ap.add_argument("--calibrate", type=int, default=0, metavar="N",
                    help="run N calibration batches and serve with "
                         "STATIC activation scales (repro.calib)")
    ap.add_argument("--clip", default="minmax",
                    choices=["minmax", "pct999", "mse"],
                    help="activation-range calibrator for --calibrate "
                         "(calib.static.act_quant_clipped)")
    ap.add_argument("--plan", default=None, metavar="FILE",
                    help="DesignPlan JSON: per-layer mixed-design decode")
    ap.add_argument("--prefill", default="fused",
                    choices=["fused", "loop"],
                    help="prompt processing: 'fused' = one full-sequence "
                         "M=B·S pass (default), 'loop' = the old token-"
                         "by-token decode loop (A/B; bit-identical)")
    ap.add_argument("--no-fuse-proj", action="store_true",
                    help="keep wq/wk/wv and w_gate/w_up as separate qdot "
                         "calls (A/B the merged-projection serving tree)")
    ap.add_argument("--continuous", type=int, default=None, metavar="N",
                    help="continuous batching: serve N total requests "
                         "through --requests slots with per-slot cache "
                         "positions (finished slots re-prefill from the "
                         "queue)")
    return ap.parse_args(argv)


def quant_config(args) -> QuantConfig:
    """The serving QuantConfig the options select: the 'fused' backend
    once static activation scales exist (--calibrate/--plan), else
    'delta' (the same bits as the 'xla' product-LUT oracle, without its
    (M, K, N) index surface)."""
    backend = args.backend or (
        "fused" if (args.calibrate or args.plan) else "delta")
    return QuantConfig(design=args.design, backend=backend,
                       mode=args.quant_mode,
                       w_per_channel=args.per_channel, inference=True)


def main(argv=None):
    args = parse_args(argv)
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    qcfg = quant_config(args)
    B = args.requests
    s_max = args.prompt_len + args.gen_len

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    params, notes = prepare_params(params, cfg, qcfg, args)
    for n in notes:
        print(f"[serve] {n}")

    if args.continuous:
        return serve_continuous(params, cfg, qcfg, args, rng)

    prompts = rng.integers(0, cfg.vocab, (B, args.prompt_len)).astype(np.int32)
    enc_out = None
    if cfg.family == "encdec":
        fr = jnp.asarray(rng.normal(size=(
            B, 16, cfg.frontend_dim or cfg.d_model)).astype(np.float32))
        enc_out = T._run_encoder(params, fr, cfg, qcfg)

    state = T.init_decode_state(cfg, B, s_max, enc_out=enc_out)
    serve_c = jax.jit(make_serve_step(cfg, qcfg),
                      donate_argnums=platform.donate(1))
    prefill_c = jax.jit(make_prefill_step(cfg, qcfg),
                        donate_argnums=platform.donate(1))
    prompts_dev = jnp.asarray(prompts)
    tok0 = jnp.zeros((B, 1), jnp.int32)

    # compile + warm up BOTH steps on a throwaway state so the loop
    # below measures steady state (first execution pays lazy init);
    # compile time is reported on its own line, not inside tok/s
    with obs.span(obs.COMPILE) as sp_compile:
        warm = T.init_decode_state(cfg, B, s_max, enc_out=enc_out)
        if args.prefill == "fused":
            # chain through the (possibly donated) warm state
            _, _, warm = prefill_c(params, warm, prompts_dev)
        jax.block_until_ready(serve_c(params, warm, tok0)[0])
        del warm

    with obs.span(obs.PREFILL) as sp_prefill:
        if args.prefill == "fused":
            tok, logits, state = prefill_c(params, state, prompts_dev)
        else:
            for i in range(args.prompt_len):
                tok, logits, state = serve_c(
                    params, state, jnp.asarray(prompts[:, i:i + 1]))
        tok.block_until_ready()

    with obs.span(obs.DECODE) as sp_decode:
        generated = [tok]
        for _ in range(args.gen_len - 1):
            tok, logits, state = serve_c(params, state, tok)
            generated.append(tok)
        out = jnp.concatenate(generated, axis=1)
        out.block_until_ready()
    t_compile, t_prefill, t_decode = (
        sp_compile.seconds, sp_prefill.seconds, sp_decode.seconds)

    n_pre = B * args.prompt_len
    n_dec = B * args.gen_len
    print(f"[serve] compile+warmup: {t_compile:.2f}s (reported separately "
          f"— steady-state rows below exclude it)")
    print(f"[serve] prefill[{args.prefill}]: {n_pre} tokens in "
          f"{t_prefill * 1e3:.1f}ms ({n_pre / t_prefill:.1f} tok/s, "
          f"{t_prefill * 1e6 / n_pre:.1f} us/tok)")
    print(f"[serve] decode: {n_dec} tokens in {t_decode * 1e3:.1f}ms "
          f"({n_dec / t_decode:.1f} tok/s, "
          f"{t_decode * 1e6 / max(args.gen_len - 1, 1):.1f} us/step)")
    dt = t_prefill + t_decode
    print(f"[serve] {B} requests, {args.gen_len} tokens each: "
          f"{dt:.2f}s steady-state, {(n_pre + n_dec) / dt:.1f} tok/s")
    print("[serve] sample output ids:", np.asarray(out[0])[:12].tolist())
    _print_counters()
    return np.asarray(out), np.asarray(logits)


if __name__ == "__main__":
    # the CLI entry point owns the persistent compile cache; tests call
    # main() directly and leave the process configuration alone
    platform.enable_compile_cache()
    main()
