"""Benchmark driver: one function per paper table/figure + kernel
micro-benchmarks.  Prints ``name,us_per_call,derived`` CSV summary lines
plus the full per-table CSVs.  ``--json`` additionally writes the
machine-readable kernel/qdot rows to BENCH_kernels.json so later PRs
have a perf baseline to diff against (CI uploads it as an artifact);
``--check-regression`` diffs a fresh run against that committed
baseline (warn-only on CPU runners, hard-fails on TPU)."""
from __future__ import annotations

import csv
import io
import json
import os
import statistics
import sys
import time


def _csv(rows) -> str:
    if not rows:
        return ""
    keys = []
    for r in rows:
        for k in r:
            if k not in keys:
                keys.append(k)
    buf = io.StringIO()
    w = csv.DictWriter(buf, fieldnames=keys)
    w.writeheader()
    for r in rows:
        w.writerow(r)
    return buf.getvalue()


def bench_stats(fn, reps: int = 7) -> dict:
    """Wall time of fn in microseconds over ``reps`` timed calls (one
    untimed compile call first): {'min_us', 'median_us'}.  The min is
    the headline metric (robust to scheduler noise); the median is what
    --check-regression compares, being stabler run-to-run."""
    import jax
    fn()  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return {"min_us": min(ts) * 1e6,
            "median_us": statistics.median(ts) * 1e6}


def bench_us(fn, reps: int = 7) -> float:
    """Min-of-reps wall microseconds (see bench_stats)."""
    return bench_stats(fn, reps)["min_us"]


def kernel_microbench():
    """Two-stage delta backend vs legacy LUT kernel vs XLA formulations
    (CPU wall time, interpret-mode pallas; the real target numbers come
    from the §Roofline analysis).  The 'delta' / 'pallas_legacy' row
    pair — both timed through the same jitted ops.approx_matmul entry
    point — is the A/B the ISSUE-2 acceptance bar reads from
    BENCH_kernels.json."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.kernels.approx_matmul import delta_matmul, lut_matmul

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 256, (256, 256)).astype(np.int32))
    b = jnp.asarray(rng.integers(0, 256, (256, 256)).astype(np.int32))
    lut = jnp.asarray(ops.get_lut("design2"))
    dlut = jnp.asarray(ops.get_delta_lut("design2"))
    F, G = ops.get_factors("design2", 16)
    rows = []

    def timed(name, fn):
        st = bench_stats(fn)
        rows.append({"kernel": name, "us_per_call": round(st["min_us"], 1),
                     "us_median": round(st["median_us"], 1),
                     "shape": "256x256x256"})

    timed("exact_matmul", lambda: ref.exact_matmul_ref(a, b))
    timed("lut_gather_xla", lambda: ref.approx_matmul_ref(a, b, lut))
    timed("residual_rank16_xla",
          lambda: ref.residual_corrected_matmul_ref(a, b, F, G))
    # the A/B the acceptance bar reads: both backends as shipped,
    # through the same jitted ops.approx_matmul entry point
    f_delta = jax.jit(lambda a, b: ops.approx_matmul(a, b, "design2",
                                                     "delta"))
    f_legacy = jax.jit(lambda a, b: ops.approx_matmul(a, b, "design2",
                                                      "pallas_legacy"))
    timed("delta", lambda: f_delta(a, b))
    timed("pallas_legacy", lambda: f_legacy(a, b))
    # raw kernels, for completeness (interpret mode off TPU)
    f_ref = jax.jit(lambda a, b: ref.delta_matmul_ref(a, b, dlut))
    timed("delta_xla_raw", lambda: f_ref(a, b))
    timed("lut_pallas_legacy_raw", lambda: lut_matmul(a, b, lut))
    timed("delta_pallas_interpret_raw", lambda: delta_matmul(a, b, dlut))
    # the fused serving kernel at microbench scale: float x in, f32 out
    # (static scales + dequant epilogue on top of the delta core)
    x = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    f_fused = jax.jit(lambda x, b: ops.fused_qdot(
        x, b, dlut, sx=0.01, zx=128.0, sw=0.01, zw=128.0,
        colsum=b.sum(0).astype(jnp.float32), lowering="xla"))
    timed("fused_qdot_xla", lambda: f_fused(x, b))

    # fused decode-step attention/cache op (the serve decode path):
    # the XLA twin as shipped + the Pallas lowering (interpret off-TPU,
    # validation-speed only — the relative row matters on real TPU)
    B_, H_, Kv_, hd_, S_ = 8, 8, 4, 64, 256
    qa = jnp.asarray(rng.normal(size=(B_, 1, H_, hd_)).astype(np.float32))
    ka = jnp.asarray(rng.normal(size=(B_, 1, Kv_, hd_)).astype(np.float32))
    va = jnp.asarray(rng.normal(size=(B_, 1, Kv_, hd_)).astype(np.float32))
    kc = jnp.zeros((B_, S_, Kv_, hd_), jnp.bfloat16)
    vc = jnp.zeros((B_, S_, Kv_, hd_), jnp.bfloat16)
    pos = jnp.full((B_,), S_ // 2, jnp.int32)

    def attn(lowering):
        return jax.jit(lambda q, k, v, kc, vc, p: ops.decode_attention(
            q, k, v, kc, vc, p, n_heads=H_, n_kv=Kv_, head_dim=hd_,
            lowering=lowering))
    f_ax = attn("xla")
    rows_shape = f"B{B_}_S{S_}_H{H_}_hd{hd_}"
    st = bench_stats(lambda: f_ax(qa, ka, va, kc, vc, pos))
    rows.append({"kernel": "decode_attn_xla",
                 "us_per_call": round(st["min_us"], 1),
                 "us_median": round(st["median_us"], 1),
                 "shape": rows_shape})
    f_ap = attn("pallas")
    st = bench_stats(lambda: f_ap(qa, ka, va, kc, vc, pos), reps=3)
    rows.append({"kernel": "decode_attn_pallas_interpret_raw",
                 "us_per_call": round(st["min_us"], 1),
                 "us_median": round(st["median_us"], 1),
                 "shape": rows_shape})

    # serving-PIPELINE A/B at compute scale, through qdot itself: the
    # unfused static path as PR 3 served it (xla product backend + STE
    # matmul + per-call compensation gathers) vs the same datapath
    # through delta_xla, vs the fused kernel (backend='fused' +
    # inference).  This is the fused-datapath win without the tiny
    # smoke model's fixed decode-step floor on top (see serve_decode).
    import dataclasses

    from repro.quant import QuantConfig, prequantize_weights, qdot
    w = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    base = QuantConfig(design="design2", backend="xla", mode="sym_i8")
    pre = prequantize_weights({"w": w}, base)["w"]
    sx = float(np.abs(np.asarray(x)).max() / 127.0)
    pre = pre.replace(act_scale=jnp.float32(sx))
    for name, backend, inference in (
            ("qdot_static_xla", "xla", False),
            ("qdot_static_delta_xla", "delta_xla", False),
            ("qdot_static_fused", "fused", True)):
        cfg = dataclasses.replace(base, backend=backend,
                                  inference=inference)
        f = jax.jit(lambda x, p=pre, c=cfg: qdot(x, p, c))
        timed(name, lambda: f(x))
    return rows


def qdot_mode_bench():
    """Signed symmetric int8 vs uint8 zero-point-decomposed qdot hot
    path: same design/backend, the sym_i8 path drops the zero-point
    cross-term matmuls (wall time + accuracy side by side)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.quant import QuantConfig, qdot

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(128, 256)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    ref_y = x @ w
    rows = []
    # mode has no effect on the disabled (exact) baseline: bench it once
    cases = [("asym_u8", "design2", "xla"),
             ("asym_u8", "design2", "residual_xla"),
             ("asym_u8", "design2", "delta_xla"),
             ("sym_i8", "design2", "xla"),
             ("sym_i8", "design2", "residual_xla"),
             ("sym_i8", "design2", "delta_xla"),
             ("asym_u8", "exact", "exact")]
    for mode, design, backend in cases:
        cfg = QuantConfig(design=design, backend=backend, mode=mode)
        fn = jax.jit(lambda x, w, c=cfg: qdot(x, w, c))
        y = fn(x, w)
        us = bench_us(lambda: fn(x, w))
        rel = float(jnp.abs(y - ref_y).mean() / jnp.abs(ref_y).mean())
        rows.append({"mode": mode, "design": design, "backend": backend,
                     "us_per_call": round(us, 1),
                     "rel_err": round(rel, 4),
                     "shape": "128x256x128"})
    return rows


def serve_decode_bench():
    """Decode-step wall time across the quantization precomputation
    ladder (quant/linear.py): dynamic -> prequantized weights ->
    +calibrated static activation scales -> +per-layer design plan,
    then the FUSED serving path on the static and plan trees (backend
    'fused' + inference mode — what launch/serve.py defaults to with
    --calibrate/--plan).  min-of-7 over 10-step windows through the
    jitted serve step on the smoke config; the fused rows vs the
    static/plan rows are the ISSUE-4 acceptance numbers."""
    import dataclasses

    import jax
    import numpy as np
    from repro import configs
    from repro.calib import (apply_calibration, apply_plan,
                             attach_comp_cols, calibrate_decode,
                             plan_designs)
    from repro.models import transformer as T
    from repro.quant import (QuantConfig, fuse_projections,
                             prequantize_weights)
    from repro.train import make_serve_step

    cfg = configs.get_smoke("qwen3-1.7b")
    B, P = 4, 4
    rows = []
    for mode in ("asym_u8", "sym_i8"):
        qcfg = QuantConfig(design="design2", backend="xla", mode=mode)
        qfused = dataclasses.replace(qcfg, backend="fused", inference=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        pp = prequantize_weights(params, qcfg)
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab, (B, P)).astype(np.int32)
        table = calibrate_decode(pp, cfg, qcfg, prompts, gen_len=2)
        sp = apply_calibration(pp, table)
        plan = plan_designs(table, qcfg, arch="qwen3-1.7b")
        mp = apply_plan(sp, plan, qcfg)
        # the fused rows serve what launch/serve.py now serves by
        # default: comp colsums cached AND projections merged
        # (fuse_projections — wqkv / w_gateup, bit-identical per column)
        spf = fuse_projections(attach_comp_cols(sp, qfused))
        mpf = fuse_projections(apply_plan(attach_comp_cols(sp, qfused),
                                          plan, qfused))
        step = jax.jit(make_serve_step(cfg, qcfg))
        step_fused = jax.jit(make_serve_step(cfg, qfused))
        base = None
        timings = {}
        for name, ps, stp in (("dynamic", params, step),
                              ("prequant", pp, step),
                              ("prequant+static", sp, step),
                              ("prequant+static+plan", mp, step),
                              ("prequant+static+fused", spf, step_fused),
                              ("prequant+static+plan+fused", mpf,
                               step_fused)):
            st = T.init_decode_state(cfg, B, P + 16)
            tok = jax.numpy.full((B, 1), 5, jax.numpy.int32)

            # single decode steps are ~1 ms on this container: time a
            # 10-step window per sample (state not donated, so every
            # call is identical work) and report the per-step min-of-7
            def window(ps=ps, st=st, tok=tok, stp=stp):
                for _ in range(10):
                    out = stp(ps, st, tok)
                return out

            stats = bench_stats(window)
            us = stats["min_us"] / 10.0
            base = base if base is not None else us
            timings[name] = us
            row = {"config": name, "mode": mode,
                   "us_per_step": round(us, 1),
                   "us_median": round(stats["median_us"] / 10.0, 1),
                   "speedup_vs_dynamic": round(base / us, 2),
                   "shape": f"B{B}_{cfg.name}"}
            if name.endswith("+fused"):
                # the fused-vs-unfused A/B on the same tree
                row["speedup_vs_unfused"] = round(
                    timings[name[:-len("+fused")]] / us, 2)
            if name.endswith("plan") or name.endswith("plan+fused"):
                row["plan_histogram"] = str(plan.histogram())
            rows.append(row)
    return rows


def serve_prefill_bench():
    """Full-sequence fused prefill vs the token-by-token prompt loop
    (what launch/serve.py shipped through PR 4): B=4 requests, P=64
    prompt tokens, on the static-calibrated fused serving tree.  The
    `token_loop` row steps the prompt through the jitted serve step
    exactly like the old driver (per-step host slice included) on the
    PR 4-era UNMERGED tree; the `fused_prefill` row is one M = B·P pass
    through make_prefill_step on the merged tree serve now defaults to.
    `speedup_vs_loop` on the fused row is the ISSUE-5 acceptance
    number."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.calib import (apply_calibration, attach_comp_cols,
                             calibrate_decode)
    from repro.models import transformer as T
    from repro.quant import (QuantConfig, fuse_projections,
                             prequantize_weights)
    from repro.train import make_prefill_step, make_serve_step

    cfg = configs.get_smoke("qwen3-1.7b")
    B, P = 4, 64
    rows = []
    for mode in ("asym_u8", "sym_i8"):
        qcfg = QuantConfig(design="design2", backend="xla", mode=mode)
        qfused = dataclasses.replace(qcfg, backend="fused", inference=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        pp = prequantize_weights(params, qcfg)
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab, (B, P)).astype(np.int32)
        cal = np.random.default_rng(1).integers(
            0, cfg.vocab, (B, 4)).astype(np.int32)
        table = calibrate_decode(pp, cfg, qcfg, cal, gen_len=2)
        spf = attach_comp_cols(apply_calibration(pp, table), qfused)
        spm = fuse_projections(spf)
        step = jax.jit(make_serve_step(cfg, qfused))
        pf = jax.jit(make_prefill_step(cfg, qfused))
        prompts_dev = jnp.asarray(prompts)
        state0 = T.init_decode_state(cfg, B, P + 8)

        def token_loop():
            st = state0
            for i in range(P):
                tok, lg, st = step(spf, st,
                                   jnp.asarray(prompts[:, i:i + 1]))
            return lg

        def fused_prefill():
            return pf(spm, state0, prompts_dev)[1]

        st_loop = bench_stats(token_loop, reps=5)
        st_pf = bench_stats(fused_prefill, reps=5)
        n = B * P
        for name, st_ in (("token_loop", st_loop),
                          ("fused_prefill", st_pf)):
            row = {"config": name, "mode": mode,
                   "us_per_token": round(st_["min_us"] / n, 1),
                   "us_median": round(st_["median_us"] / n, 1),
                   "tok_s": round(n / (st_["min_us"] * 1e-6), 0),
                   "shape": f"B{B}_P{P}_{cfg.name}"}
            if name == "fused_prefill":
                row["speedup_vs_loop"] = round(
                    st_loop["min_us"] / st_["min_us"], 2)
            rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# Regression check against the committed baseline
# ---------------------------------------------------------------------------

# table -> (row-identity fields, headline metric field)
_REGRESSION_SPEC = {"kernel_microbench": (("kernel",), "us_per_call"),
                    "serve_decode": (("config", "mode"), "us_per_step"),
                    "serve_prefill": (("config", "mode"), "us_per_token")}


def compare_to_baseline(baseline: dict, fresh: dict, tol: float):
    """Diff fresh kernel_microbench/serve_decode rows against a
    committed BENCH_kernels.json payload.  Rows are matched by identity
    fields; the comparison metric is the median when both sides carry
    one (stabler run-to-run), else the headline min.  Returns (report,
    regressions): regressions are rows whose fresh/baseline ratio
    exceeds ``tol``."""
    report, regressions = [], []
    for table, (keys, metric) in _REGRESSION_SPEC.items():
        base = {tuple(r.get(k) for k in keys): r
                for r in baseline.get("benchmarks", {}).get(table, [])}
        for r in fresh.get(table, []):
            b = base.get(tuple(r.get(k) for k in keys))
            if b is None:
                continue     # new row — nothing to regress against
            if "us_median" in b and "us_median" in r:
                bv, fv = b["us_median"], r["us_median"]
            else:
                bv, fv = b.get(metric), r.get(metric)
            if not bv or not fv:
                continue
            row = {"table": table,
                   "row": "/".join(str(r.get(k)) for k in keys),
                   "baseline_us": round(bv, 1), "fresh_us": round(fv, 1),
                   "ratio": round(fv / bv, 2)}
            report.append(row)
            if fv / bv > tol:
                regressions.append(row)
    return report, regressions


def main(argv=None) -> None:
    import argparse
    if __package__:
        from . import tables
    else:  # `python benchmarks/run.py`: sys.path[0] is benchmarks/
        import tables
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of table names to run "
                         "(also matches 'kernel_microbench'/'qdot_modes'); "
                         "default runs everything")
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json",
                    default=None, metavar="PATH",
                    help="also write the kernel_microbench/qdot_modes rows "
                         "as JSON (default path: BENCH_kernels.json) — the "
                         "machine-readable perf trajectory CI archives")
    ap.add_argument("--check-regression", nargs="?",
                    const="BENCH_kernels.json", default=None,
                    metavar="BASELINE",
                    help="compare fresh kernel_microbench/serve_decode "
                         "medians against a committed baseline JSON "
                         "(default BENCH_kernels.json, read BEFORE --json "
                         "overwrites it).  Hard-fails on TPU runners or "
                         "with REPRO_BENCH_STRICT=1; warn-only on CPU "
                         "(container timing is too noisy to gate on)")
    ap.add_argument("--regression-tol", type=float, default=1.6,
                    metavar="RATIO",
                    help="fresh/baseline ratio above which a row counts "
                         "as a regression (default 1.6)")
    args = ap.parse_args(argv)
    baseline = None
    if args.check_regression:
        if os.path.exists(args.check_regression):
            with open(args.check_regression) as fh:
                baseline = json.load(fh)
        else:
            print(f"[regression] no baseline at {args.check_regression}; "
                  f"skipping the check (first run?)")
    only = set(args.only.split(",")) if args.only else None
    if only:
        known = set(tables.ALL) | {"kernel_microbench", "qdot_modes",
                                   "serve_decode", "serve_prefill"}
        unknown = only - known
        if unknown:
            ap.error(f"unknown benchmark name(s) {sorted(unknown)}; "
                     f"choose from {sorted(known)}")

    def wanted(name):
        return only is None or name in only

    t_all = time.perf_counter()
    summary = []
    for name, fn in tables.ALL.items():
        if not wanted(name):
            continue
        t0 = time.perf_counter()
        rows = fn()
        dt = (time.perf_counter() - t0) * 1e6
        print(f"### {name}")
        print(_csv(rows))
        summary.append((name, dt, len(rows)))
    json_out = {}
    for name, fn in (("kernel_microbench", kernel_microbench),
                     ("qdot_modes", qdot_mode_bench),
                     ("serve_decode", serve_decode_bench),
                     ("serve_prefill", serve_prefill_bench)):
        if wanted(name):
            rows = fn()
            print(f"### {name}")
            print(_csv(rows))
            json_out[name] = rows

    if baseline is not None:
        report, regressions = compare_to_baseline(baseline, json_out,
                                                  args.regression_tol)
        print("### regression_check  (vs "
              f"{args.check_regression}, tol {args.regression_tol}x)")
        print(_csv(report))
        if regressions:
            import jax
            strict = (jax.default_backend() == "tpu"
                      or os.environ.get("REPRO_BENCH_STRICT") == "1")
            msg = (f"[regression] {len(regressions)} row(s) slower than "
                   f"{args.regression_tol}x baseline: "
                   + ", ".join(f"{r['row']} ({r['ratio']}x)"
                               for r in regressions))
            if strict:
                print(msg, file=sys.stderr)
                sys.exit(1)
            print(msg + "  (warn-only on this CPU runner)")
        elif report:
            print(f"[regression] OK: {len(report)} rows within "
                  f"{args.regression_tol}x of baseline")

    if args.json and not json_out:
        print(f"[json] skipped {args.json}: --only excluded "
              f"kernel_microbench, qdot_modes, serve_decode and "
              f"serve_prefill (nothing to record)")
    elif args.json:
        import platform
        payload = {"benchmarks": json_out,
                   "meta": {"python": platform.python_version(),
                            "platform": platform.platform(),
                            "unix_time": int(time.time())}}
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"[json] wrote {args.json} "
              f"({sum(len(v) for v in json_out.values())} rows)")

    print("### summary  (name,us_per_call,derived)")
    for name, dt, n in summary:
        print(f"{name},{dt:.0f},{n}_rows")
    print(f"total_wall_s,{time.perf_counter() - t_all:.1f}")


if __name__ == "__main__":
    # the CLI entry point owns the persistent compile cache
    from repro.kernels.platform import enable_compile_cache
    enable_compile_cache()
    main()
