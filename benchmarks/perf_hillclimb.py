"""§Perf hillclimb driver: the three selected cells, iterated.

Each iteration: hypothesis -> change (config knob) -> re-lower ->
before/after roofline terms -> confirmed/refuted.  Results append to
experiments/perf_iterations.json; EXPERIMENTS.md §Perf narrates them.

Cells (selection rationale in EXPERIMENTS.md):
  A nemotron-4-340b train_4k   — worst memory term / does not fit
  B mixtral-8x7b   train_4k    — most collective-bound + expert layout
  C qwen3-1.7b     train_4k    — paper-technique cell (backend sweep)

Also hosts the delta-kernel block-shape autotuner (``--autotune-delta``):
sweeps (TM, TN, TK) for kernels.approx_matmul.delta_matmul and times
the fused serving qdot (ops.fused_qdot, per quant mode: the one-hot
kernel and the twin's k_block) on a fixed matmul shape, recording to
experiments/delta_autotune.json; and the serving-step tuner
(``--autotune-serve``): the fused qdot at the PREFILL shape
(M = B·S — a new tile regime: tall activations against the same
weights) plus the decode-attention kernel's cache-tile (block_s) space
(kernels.attention.decode_attention_step).

Usage:
  PYTHONPATH=src python -m benchmarks.perf_hillclimb --iter A1 [A2 ...]
  PYTHONPATH=src python -m benchmarks.perf_hillclimb --autotune-delta
  PYTHONPATH=src python -m benchmarks.perf_hillclimb --autotune-serve
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# candidate (TM, TN, TK) tiles: MXU-aligned down to VPU-lane-sized.  The
# per-tile gather surface is TM*TK*TN * 2 B (int16) — 4 MiB at 128^3,
# 512 KiB at TK=64 with 128x128 out tiles — so smaller TK trades gather
# buffer for more K-grid revisits of the accumulator tile.
DELTA_BLOCK_CANDIDATES = [
    (128, 128, 128), (128, 128, 64), (128, 128, 32),
    (64, 128, 128), (128, 64, 128), (64, 64, 128),
    (64, 64, 64), (256, 128, 64),
]


DELTA_REF_KB_CANDIDATES = [8, 16, 32, 64]


def autotune_delta(shape=(256, 256, 256), design: str = "design2",
                   signed: bool = False,
                   out: str = "experiments/delta_autotune.json"):
    """Time the two delta lowerings across their tile knobs and record
    the winners: (TM,TN,TK) for the Pallas kernel (interpret mode off
    TPU — the relative ordering is the point), k_block for the XLA twin.

    Blocks larger than the (padded) problem are skipped.  Results append
    to ``out`` so successive runs build a trajectory per shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.kernels.approx_matmul import delta_matmul

    M, K, N = shape
    rng = np.random.default_rng(0)
    lo, hi = (-128, 128) if signed else (0, 256)
    a = jnp.asarray(rng.integers(lo, hi, (M, K)).astype(np.int32))
    b = jnp.asarray(rng.integers(lo, hi, (K, N)).astype(np.int32))
    dlut_np = ops.get_delta_lut(design, signed)
    dlut = jnp.asarray(dlut_np)
    off = 128 if signed else 0

    if __package__:
        from .run import bench_us
    else:  # `python benchmarks/perf_hillclimb.py`
        from run import bench_us

    # delta_matmul pads operands up, so blocks larger than the problem
    # still work — but benchmarking them would time mostly padding.
    # Always keep at least the smallest candidate so tiny shapes tune.
    blocks = [blk for blk in DELTA_BLOCK_CANDIDATES
              if blk[0] <= M and blk[1] <= N and blk[2] <= K] \
        or [min(DELTA_BLOCK_CANDIDATES, key=lambda blk: blk[0]*blk[1]*blk[2])]
    pallas_results = []
    for block in blocks:
        us = bench_us(
            lambda: delta_matmul(a, b, dlut, block=block, offset=off), reps=5)
        pallas_results.append({"block": list(block),
                               "us_per_call": round(us, 1)})
        print(f"  pallas block={block}: {us:.0f} us")

    # only sweep k_blocks that divide K: delta_matmul_ref silently falls
    # back to a smaller divisor otherwise, and timing the same effective
    # config four times would record a winner that never ran
    kbs = [kb for kb in DELTA_REF_KB_CANDIDATES if K % kb == 0]
    if not kbs:
        kbs = [next(kb for kb in (32, 16, 8, 4, 2, 1) if K % kb == 0)]
    ref_results = []
    for kb in kbs:
        f = jax.jit(lambda a, b, kb=kb: ref.delta_matmul_ref(
            a, b, dlut_np, offset=off, k_block=kb))
        us = bench_us(lambda: f(a, b), reps=5)
        ref_results.append({"k_block": kb, "us_per_call": round(us, 1)})
        print(f"  xla k_block={kb}: {us:.0f} us")

    record = {
        "shape": list(shape), "design": design, "signed": signed,
        "pallas": {"results": pallas_results,
                   "best": min(pallas_results,
                               key=lambda r: r["us_per_call"])},
        "xla": {"results": ref_results,
                "best": min(ref_results, key=lambda r: r["us_per_call"])},
    }
    os.makedirs(os.path.dirname(out), exist_ok=True)
    hist = json.load(open(out)) if os.path.exists(out) else []
    hist.append(record)
    json.dump(hist, open(out, "w"), indent=1)
    print(f"[autotune] {design} {'signed' if signed else 'unsigned'} "
          f"{M}x{K}x{N}: pallas best={tuple(record['pallas']['best']['block'])}"
          f" ({record['pallas']['best']['us_per_call']:.0f} us), "
          f"xla best kb={record['xla']['best']['k_block']} "
          f"({record['xla']['best']['us_per_call']:.0f} us) -> {out}")
    return record


def autotune_fused(shape=(256, 256, 256), design: str = "design2",
                   out: str = "experiments/delta_autotune.json"):
    """Time the fused serving qdot's one-hot kernel (its blocks come
    from the shape) per quant mode (asym_u8 / sym_i8) and learn the XLA
    twin's k_block, recording the results to ``out``.  Off-TPU the
    kernel runs in interpret mode: re-run on hardware for real
    numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    if __package__:
        from .run import bench_us
    else:
        from run import bench_us

    M, K, N = shape
    rng = np.random.default_rng(0)
    xnp = rng.normal(size=(M, K)).astype(np.float32)
    x = jnp.asarray(xnp)
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    records = []
    for mode in ("asym_u8", "sym_i8"):
        # static quantizers computed the real pipeline's way
        # (repro.quant.quantize), so the sweep sees the operand
        # distribution serving actually produces
        from repro.quant.quantize import quantize_int8, quantize_uint8
        signed = mode == "sym_i8"
        if signed:
            qw, sw_a = quantize_int8(w)
            sw = float(sw_a)
            zx = zw = colsum = None
            sx = max(float(np.abs(xnp).max()) / 127.0, 1e-8)
        else:
            qw, sw_a, zw_a = quantize_uint8(w)
            sw, zw = float(sw_a), float(zw_a)
            colsum = np.asarray(qw).sum(0).astype(np.float32)
            lo, hi = float(xnp.min()), float(xnp.max())
            sx = max((hi - lo) / 255.0, 1e-8)
            zx = float(np.clip(np.round(-lo / sx), 0, 255))
        dlut = jnp.asarray(ops.get_delta_lut(design, signed))

        def fused(lowering, **kw):
            return jax.jit(lambda x, qw: ops.fused_qdot(
                x, qw, dlut, sx=sx, zx=zx, sw=sw, zw=zw, colsum=colsum,
                signed=signed, lowering=lowering, **kw))

        # the one-hot kernel takes its blocks from the shape: one point
        f = fused("pallas")
        us = bench_us(lambda: f(x, qw), reps=3)
        pallas_results = [{"us_per_call": round(us, 1)}]
        print(f"  fused[{mode}] pallas (one-hot): {us:.0f} us")
        kbs = [kb for kb in DELTA_REF_KB_CANDIDATES if K % kb == 0] \
            or [next(kb for kb in (32, 16, 8, 4, 2, 1) if K % kb == 0)]
        xla_results = []
        for kb in kbs:
            f = fused("xla", k_block=kb)
            us = bench_us(lambda: f(x, qw), reps=3)
            xla_results.append({"k_block": kb, "us_per_call": round(us, 1)})
            print(f"  fused[{mode}] xla k_block={kb}: {us:.0f} us")
        rec = {
            "kind": "fused", "shape": list(shape), "design": design,
            "mode": mode,
            "pallas": {"results": pallas_results,
                       "best": min(pallas_results,
                                   key=lambda r: r["us_per_call"])},
            "xla": {"results": xla_results,
                    "best": min(xla_results,
                                key=lambda r: r["us_per_call"])},
        }
        records.append(rec)
        pb = rec["pallas"]["best"]
        print(f"[autotune] fused {mode} {design} {M}x{K}x{N}: pallas "
              f"({pb['us_per_call']:.0f} us), xla best "
              f"kb={rec['xla']['best']['k_block']} "
              f"({rec['xla']['best']['us_per_call']:.0f} us)")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    hist = json.load(open(out)) if os.path.exists(out) else []
    hist.extend(records)
    json.dump(hist, open(out, "w"), indent=1)
    print(f"[autotune] fused winners appended -> {out}")
    return records


DECODE_ATTN_BLOCK_S = [32, 64, 128, 256]


def autotune_decode_attn(B: int = 8, S: int = 512, H: int = 16,
                         Kv: int = 8, hd: int = 64,
                         out: str = "experiments/delta_autotune.json"):
    """Sweep the fused decode-attention kernel's cache-tile size
    ``block_s`` (kernels.attention.decode_attention_step — the online-
    softmax S-tiling knob) against the XLA twin, recording winners to
    ``out``.  Off-TPU the Pallas sweep runs interpret mode — the
    relative tile ordering is the point; re-run on hardware."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    if __package__:
        from .run import bench_us
    else:
        from run import bench_us

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, 1, Kv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, 1, Kv, hd)).astype(np.float32))
    kc = jnp.zeros((B, S, Kv, hd), jnp.bfloat16)
    vc = jnp.zeros((B, S, Kv, hd), jnp.bfloat16)
    pos = jnp.full((B,), S // 2, jnp.int32)

    def f(lowering, block_s=128):
        return jax.jit(lambda q, k, v, kc, vc, p: ops.decode_attention(
            q, k, v, kc, vc, p, n_heads=H, n_kv=Kv, head_dim=hd,
            lowering=lowering, block_s=block_s))

    results = []
    for bs in [b for b in DECODE_ATTN_BLOCK_S if b <= S]:
        g = f("pallas", bs)
        us = bench_us(lambda: g(q, k, v, kc, vc, pos), reps=3)
        results.append({"block_s": bs, "us_per_call": round(us, 1)})
        print(f"  decode_attn pallas block_s={bs}: {us:.0f} us")
    g = f("xla")
    xla_us = bench_us(lambda: g(q, k, v, kc, vc, pos), reps=5)
    print(f"  decode_attn xla twin: {xla_us:.0f} us")
    record = {
        "kind": "decode_attn", "shape": [B, S, H, Kv, hd],
        "pallas": {"results": results,
                   "best": min(results, key=lambda r: r["us_per_call"])},
        "xla": {"us_per_call": round(xla_us, 1)},
    }
    os.makedirs(os.path.dirname(out), exist_ok=True)
    hist = json.load(open(out)) if os.path.exists(out) else []
    hist.append(record)
    json.dump(hist, open(out, "w"), indent=1)
    best = record["pallas"]["best"]
    print(f"[autotune] decode_attn B{B} S{S} H{H} hd{hd}: pallas best "
          f"block_s={best['block_s']} ({best['us_per_call']:.0f} us) "
          f"-> {out}")
    return record


def run_iteration(tag: str):
    # import inside so XLA_FLAGS from dryrun module applies first
    from repro.launch import dryrun
    from repro.quant import QuantConfig

    ITERS = {
        # --- cell A: nemotron train (memory term) ---
        "A0": dict(arch="nemotron-4-340b", shape="train_4k",
                   hypothesis="baseline (rank16 residual, mb=1)"),
        "A1": dict(arch="nemotron-4-340b", shape="train_4k", microbatches=16,
                   hypothesis="temp is dominated by microbatch-linear "
                              "activations+logits; mb=16 cuts temp ~10x"),
        "A2": dict(arch="nemotron-4-340b", shape="train_4k", microbatches=64,
                   hypothesis="mb=64 pushes temp under 2x HBM; collective "
                              "term roughly unchanged (per-step grads)"),
        # --- cell B: mixtral train (collective term / expert layout) ---
        "B0": dict(arch="mixtral-8x7b", shape="train_4k",
                   hypothesis="baseline before expert-TP fallback"),
        "B1": dict(arch="mixtral-8x7b", shape="train_4k",
                   hypothesis="8 experts < 16 model axis left experts "
                              "UNSHARDED on model; TP-on-ffn fallback "
                              "shards 3.76TB of expert weight 16x -> temp "
                              "and weight-gather collectives both drop"),
        "B2": dict(arch="mixtral-8x7b", shape="train_4k", microbatches=16,
                   hypothesis="remaining temp is dispatch+logits; mb=16 "
                              "divides it"),
        # --- cell C: qwen3 train (compute term vs emulation fidelity) ---
        "C0": dict(arch="qwen3-1.7b", shape="train_4k", rank=16,
                   hypothesis="baseline rank-16 residual emulation: "
                              "compute term 17x model flops"),
        "C1": dict(arch="qwen3-1.7b", shape="train_4k", rank=4,
                   hypothesis="rank 4 cuts emulation factor 17->5 "
                              "(fraction x3.4) at residual-MED 186 vs 353 "
                              "fidelity (53% of error mass captured)"),
        "C2": dict(arch="qwen3-1.7b", shape="train_4k", rank=1,
                   hypothesis="rank 1 -> factor 2: near-pure-MXU; only "
                              "the rank-1 separable error mode retained "
                              "(41%); the quality/perf knee"),
        "C3": dict(arch="qwen3-1.7b", shape="train_4k", backend="exact",
                   hypothesis="upper bound: fake-quant STE without error "
                              "emulation (factor 1) — what QAT-for-"
                              "deployment would run"),
    }
    spec = dict(ITERS[tag])
    arch = spec.pop("arch")
    shape = spec.pop("shape")
    hypo = spec.pop("hypothesis")
    mb = spec.pop("microbatches", 1)
    qcfg = QuantConfig(design="design2",
                       backend=spec.pop("backend", "residual_xla"),
                       rank=spec.pop("rank", 16))
    res = dryrun.lower_cell(arch, shape, multi_pod=False, qcfg=qcfg,
                            microbatches=mb,
                            extra={"iteration": tag, "hypothesis": hypo})
    out = "experiments/perf_iterations.json"
    hist = json.load(open(out)) if os.path.exists(out) else []
    hist.append(res)
    json.dump(hist, open(out, "w"), indent=1)
    gib = res["bytes_per_device"] / 2**30
    coll = sum(res.get("collectives_extrapolated",
                       res["collectives"]).values())
    fl = res.get("flops_extrapolated", res["flops"])
    print(f"{tag}: {arch}/{shape} mb={mb} rank={qcfg.rank} "
          f"backend={qcfg.backend}")
    print(f"  -> {fl:.3e} flops/dev, {gib:.2f} GiB/dev, "
          f"coll={coll:.3e} B/dev")
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--iter", nargs="+", default=[])
    ap.add_argument("--autotune-delta", action="store_true",
                    help="sweep delta_matmul (TM,TN,TK) block shapes AND "
                         "the fused kernel's (TM,TN,TK,TKsub) space per "
                         "quant mode; record winners to experiments/"
                         "delta_autotune.json")
    ap.add_argument("--autotune-serve", action="store_true",
                    help="learn the serving step's new tile regimes: the "
                         "fused kernel's (TM,TN,TK,TKsub) point at the "
                         "PREFILL shape (M = B·S — --prefill-shape) and "
                         "the decode-attention kernel's block_s space; "
                         "appended to experiments/delta_autotune.json")
    ap.add_argument("--shape", default="256,256,256",
                    help="M,K,N for --autotune-delta")
    ap.add_argument("--prefill-shape", default="512,256,256",
                    help="M,K,N for the --autotune-serve prefill point "
                         "(M = B·S)")
    ap.add_argument("--signed", action="store_true",
                    help="autotune the signed (int8-operand) path")
    args = ap.parse_args()
    if not args.iter and not args.autotune_delta and not args.autotune_serve:
        ap.error("nothing to do: pass --iter, --autotune-delta and/or "
                 "--autotune-serve")
    for tag in args.iter:
        run_iteration(tag)
    if args.autotune_delta:
        shape = tuple(int(x) for x in args.shape.split(","))
        autotune_delta(shape, signed=args.signed)
        autotune_fused(shape)
    if args.autotune_serve:
        pshape = tuple(int(x) for x in args.prefill_shape.split(","))
        autotune_fused(pshape)      # the M = B·S prefill tile regime
        autotune_decode_attn()
