"""Smoke check of the serving path on one TPU chip.

    python chip_smoke.py

Serves qwen3-1.7b at its published widths (28 layers, d_model 2048,
vocab 151,936; random weights from SEED) through the normal entry
point — launch/serve.py's prepare_params (--calibrate 1: static scales,
the 'fused' backend, merged projections) -> make_prefill_step ->
make_serve_step — and checks what comes out.  One process, no child
processes.  Phases, one line each with its seconds:

  (a) device: the script exits non-zero, with no result line, unless
      JAX's first device is a TPU;
  (b) integer core: ops.approx_matmul(backend='delta') at M=8, K=2048,
      N=256 (design2, unsigned and signed) equals the gate-level LUT
      product computed with numpy on the host;
  (c) decode attention: the Pallas kernel at qwen3 widths with bf16
      caches agrees with ref.decode_attention_ref — cache rows bit for
      bit, the output within ATTN_ATOL;
  (d) serving, for asym_u8 and sym_i8: tokens inside the vocabulary,
      finite logits, and identical tokens when prefill and decode are
      repeated from a fresh state.

The times it prints come from a single smoke run (compilation reported
on its own line); they are not a benchmark.  Any failure exits
non-zero.  The last line of standard output is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

ARCH = "qwen3-1.7b"
SEED = 0
DESIGN = "design2"
# The width and depth are the published ones; the traffic is cut to fit
# the run in 20 minutes: the eager calibration runs the qdot's
# blocked-XLA twin, which gathers one delta-table entry per MAC (about
# 0.14 G lookups/s on a v5e), so each prompt row costs seconds there.
REQUESTS, PROMPT_LEN, GEN_LEN = 2, 2, 3
# Phase (c): the kernel and the twin both contract in f32 at HIGHEST
# precision; they differ by f32 reassociation (online against two-pass
# softmax) and the transcendental implementations of Mosaic and XLA.
# Outputs are convex combinations of N(0, 1) cache values.
ATTN_ATOL = 1e-4


def log(phase: str, msg: str, seconds: float) -> None:
    print(f"[chip_smoke] {phase}: {msg} ({seconds:.3f} s)", flush=True)


def phase_device() -> dict:
    """(a) The device as JAX reports it; raises unless it is a TPU."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's first device is {d.platform!r} "
                           f"({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_integer_core(M: int = 8, K: int = 2048, N: int = 256,
                       design: str = DESIGN) -> None:
    """(b) The bit-exact approximate matmul against the host LUT
    product.  The op returns float32; the comparison is exact (both
    sides round the same integer sum to float32)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    rng = np.random.default_rng(SEED)
    for signed in (False, True):
        lo, hi, off = (-128, 128, 128) if signed else (0, 256, 0)
        a = rng.integers(lo, hi, (M, K)).astype(np.int32)
        b = rng.integers(lo, hi, (K, N)).astype(np.int32)
        lut = (ops.get_signed_lut(design) if signed
               else ops.get_lut(design)).astype(np.int64)
        want = lut[a[:, :, None] + off, b[None, :, :] + off].sum(axis=1)
        got = np.asarray(ops.approx_matmul(jnp.asarray(a), jnp.asarray(b),
                                           design, "delta", 32, signed))
        if not np.array_equal(got, want.astype(np.float32)):
            bad = int((got != want.astype(np.float32)).sum())
            raise AssertionError(f"approx_matmul(delta, signed={signed}) "
                                 f"differs from the host LUT product in "
                                 f"{bad} of {got.size} entries")


def phase_decode_attention(cfg, B: int = 4, S: int = 512) -> float:
    """(c) The decode-attention Pallas kernel against the XLA twin (run
    at HIGHEST matmul precision), uniform and per-slot positions.
    Returns the largest output difference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    rng = np.random.default_rng(SEED)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    q, k, v = f32(B, 1, H, hd), f32(B, 1, KV, hd), f32(B, 1, KV, hd)
    kc = f32(B, S, KV, hd).astype(jnp.bfloat16)
    vc = f32(B, S, KV, hd).astype(jnp.bfloat16)
    gains = (f32(hd), f32(hd)) if cfg.qk_norm else (None, None)
    kw = dict(n_heads=H, n_kv=KV, head_dim=hd, rope_theta=cfg.rope_theta,
              q_gain=gains[0], k_gain=gains[1])
    kernel = jax.jit(lambda *a: ops.decode_attention(*a, lowering="pallas",
                                                     **kw))
    twin_jit = jax.jit(lambda *a: ref.decode_attention_ref(*a, **kw))

    def twin(*a):
        with jax.default_matmul_precision("highest"):
            return twin_jit(*a)

    worst = 0.0
    for idx in (jnp.int32(S // 2),
                jnp.asarray(rng.integers(0, S, (B,)), jnp.int32)):
        o_k, ck_k, cv_k = kernel(q, k, v, kc, vc, idx)
        o_t, ck_t, cv_t = twin(q, k, v, kc, vc, idx)
        for got, want in ((ck_k, ck_t), (cv_k, cv_t)):
            if not np.array_equal(np.asarray(got), np.asarray(want)):
                raise AssertionError("decode attention: cache rows differ "
                                     "from the twin's")
        err = float(np.abs(np.asarray(o_k) - np.asarray(o_t)).max())
        if not err <= ATTN_ATOL:
            raise AssertionError(f"decode attention: output differs from "
                                 f"the twin by {err} > {ATTN_ATOL}")
        worst = max(worst, err)
    return worst


def phase_serve(arch: str, mode: str, *, smoke: bool = False,
                requests: int = REQUESTS, prompt_len: int = PROMPT_LEN,
                gen_len: int = GEN_LEN) -> dict:
    """(d) Prepare, compile, prefill and decode through the serving
    entry points, twice from a fresh state.  Returns the timings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.kernels import platform
    from repro.launch import serve
    from repro.models import transformer as T
    from repro.train import make_prefill_step, make_serve_step

    argv = ["--arch", arch, "--calibrate", "1", "--quant-mode", mode,
            "--design", DESIGN, "--requests", str(requests),
            "--prompt-len", str(prompt_len), "--gen-len", str(gen_len)]
    args = serve.parse_args(argv + (["--smoke"] if smoke else []))
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    qcfg = serve.quant_config(args)
    B, P, G = requests, prompt_len, gen_len
    s_max = P + G
    t = {}

    t0 = time.perf_counter()
    params, notes = serve.prepare_params(
        T.init_params(jax.random.PRNGKey(SEED), cfg), cfg, qcfg, args)
    jax.block_until_ready(params)
    t["prepare_s"] = time.perf_counter() - t0

    prompts = jnp.asarray(np.random.default_rng(SEED).integers(
        0, cfg.vocab, (B, P)).astype(np.int32))
    state_spec = jax.eval_shape(lambda: T.init_decode_state(cfg, B, s_max))
    tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t0 = time.perf_counter()
    prefill = jax.jit(make_prefill_step(cfg, qcfg),
                      donate_argnums=platform.donate(1)).lower(
        params, state_spec, prompts).compile()
    step = jax.jit(make_serve_step(cfg, qcfg),
                   donate_argnums=platform.donate(1)).lower(
        params, state_spec, tok_spec).compile()
    t["compile_s"] = time.perf_counter() - t0

    runs = []
    for _ in range(2):
        state = T.init_decode_state(cfg, B, s_max)
        t0 = time.perf_counter()
        tok, logits_p, state = prefill(params, state, prompts)
        tok.block_until_ready()
        t_pre = time.perf_counter() - t0
        toks = [tok]
        t0 = time.perf_counter()
        for _ in range(G - 1):
            tok, logits_d, state = step(params, state, tok)
            toks.append(tok)
        out = np.asarray(jnp.concatenate(toks, axis=1))
        t_dec = time.perf_counter() - t0
        logits = [np.asarray(logits_p)]
        if G > 1:
            logits.append(np.asarray(logits_d))
        runs.append((out, logits, t_pre, t_dec))

    (out, logits, t["prefill_s"], t["decode_s"]), (out2, *_) = runs
    if out.shape != (B, G) or not ((out >= 0) & (out < cfg.vocab)).all():
        raise AssertionError(f"{mode}: tokens {out.tolist()} outside "
                             f"[0, {cfg.vocab}) or not of shape {(B, G)}")
    if not all(np.isfinite(lg).all() for lg in logits):
        raise AssertionError(f"{mode}: non-finite logits")
    if not np.array_equal(out, out2):
        raise AssertionError(f"{mode}: repeating prefill and decode from a "
                             f"fresh state gave other tokens: "
                             f"{out.tolist()} vs {out2.tolist()}")
    t["tokens"] = out.tolist()
    t["notes"] = notes
    return t


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main() -> int:
    from repro.kernels import platform
    cache = platform.enable_compile_cache()

    t0 = time.perf_counter()
    try:
        device = phase_device()
    except RuntimeError as e:
        print(f"[chip_smoke] (a) device: {e}", file=sys.stderr)
        return 1
    log("(a) device", json.dumps(device) + f", compile cache {cache}",
        time.perf_counter() - t0)

    from repro import configs
    cfg = configs.get(ARCH)
    print("[chip_smoke] lowerings: " + json.dumps({
        "qdot (fused backend)": platform.lowering("qdot"),
        "decode attention": platform.lowering("decode_attention"),
        "prefill attention": "xla (models.layers.attention)",
        "unembed": "xla (f32 matmul)"}), flush=True)

    t0 = time.perf_counter()
    phase_integer_core()
    log("(b) integer core", f"approx_matmul(delta, {DESIGN}) M=8 K=2048 "
        f"N=256 equals the host LUT product, unsigned and signed",
        time.perf_counter() - t0)

    t0 = time.perf_counter()
    err = phase_decode_attention(cfg)
    log("(c) decode attention", f"Pallas kernel vs twin at H={cfg.n_heads} "
        f"Kv={cfg.n_kv} hd={cfg.hd} S=512 bf16 caches: cache rows equal, "
        f"max |out diff| {err:.3e} <= {ATTN_ATOL:g}",
        time.perf_counter() - t0)

    for mode in ("asym_u8", "sym_i8"):
        t0 = time.perf_counter()
        t = phase_serve(ARCH, mode)
        log(f"(d) serve {mode}", f"{ARCH} full width, {REQUESTS} requests, "
            f"prompt {PROMPT_LEN}, {GEN_LEN} new tokens: tokens "
            f"{t['tokens']}", time.perf_counter() - t0)
        print(f"[chip_smoke]   {mode} prepare (calibration included): "
              f"{t['prepare_s']:.3f} s; compile (prefill + decode step): "
              f"{t['compile_s']:.3f} s", flush=True)
        print(f"[chip_smoke]   {mode} single smoke run, not a benchmark: "
              f"prefill {t['prefill_s']:.3f} s, {GEN_LEN - 1} decode steps "
              f"{t['decode_s']:.3f} s "
              f"({t['decode_s'] / max(GEN_LEN - 1, 1):.3f} s/step)",
              flush=True)
        print(f"[chip_smoke]   {mode} peak_bytes_in_use so far: "
              f"{peak_bytes()}", flush=True)

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
