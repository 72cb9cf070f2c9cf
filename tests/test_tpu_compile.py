"""Compiles for a described TPU v5e chip (no chip attached): the kernels
of the serving path at qwen3-1.7b's published widths, lowered the way
kernels.platform dispatches them on the TPU.  The TPU compiler refuses
here what it would refuse on the chip (a kernel Mosaic cannot lower, a
block that breaks the tiling), at no chip time.  Nothing runs, so these
tests say nothing about results or speed.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler library, and every worker collects
the same tests.  The persistent compile cache is off around the
compiles (a compile for a described chip cannot be read back without
one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops, platform

CFG = configs.get("qwen3-1.7b")
HD = CFG.hd


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer kernels.platform to its TPU choices while tracing: the
    process itself runs on the CPU backend.  A jitted kernel's trace is
    cached by its arguments' shapes, not by the platform it was traced
    for, so the caches are cleared on both sides: no trace made for the
    CPU is reused here, and none made here is reused by a later test
    in this process."""
    jax.clear_caches()
    monkeypatch.setattr(platform, "backend", lambda: "tpu")
    yield
    jax.clear_caches()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("S", [5, 512, 2050])
@pytest.mark.parametrize("per_slot", [False, True])
def test_decode_attention_compiles(one_chip, on_tpu, per_slot, S):
    """The decode-attention Pallas kernel with the bf16 caches the
    server holds (models.layers.make_cache), through ops.decode_attention
    (qk-norm + rope + append in XLA around the kernel).  Cache lengths:
    one tile shorter than the bf16 sublane tile (S_max = prompt + new
    tokens of a short request), whole tiles, and a ragged last tile."""
    B = 4
    H, KV = CFG.n_heads, CFG.n_kv
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    args = (s((B, 1, H, HD), jnp.float32), s((B, 1, KV, HD), jnp.float32),
            s((B, 1, KV, HD), jnp.float32),
            s((B, S, KV, HD), jnp.bfloat16), s((B, S, KV, HD), jnp.bfloat16),
            s((B,) if per_slot else (), jnp.int32),
            s((HD,), jnp.float32), s((HD,), jnp.float32))

    def step(q, k, v, kc, vc, idx, qg, kg):
        return ops.decode_attention(q, k, v, kc, vc, idx, n_heads=H,
                                    n_kv=KV, head_dim=HD,
                                    rope_theta=CFG.rope_theta,
                                    q_gain=qg, k_gain=kg)

    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the Pallas kernel


# (K, N) of the merged serving projections: wqkv, w_gateup, w_down
QDOT_SHAPES = [
    (CFG.d_model, (CFG.n_heads + 2 * CFG.n_kv) * HD),
    (CFG.d_model, 2 * CFG.d_ff),
    (CFG.d_ff, CFG.d_model),
]


@pytest.mark.parametrize("mode", ["asym_u8", "sym_i8"])
@pytest.mark.parametrize("M", [4, 12, 16, 32, 64])  # decode ... prefill
@pytest.mark.parametrize("K,N", QDOT_SHAPES)
def test_fused_qdot_compiles(one_chip, on_tpu, K, N, M, mode):
    """The fused serving qdot as the TPU dispatches it (the Pallas
    kernel onehot_qdot, see kernels.platform), per-channel scales and
    compensation tables as the calibrated serving tree carries them:
    one Pallas call, and no loop around it."""
    signed = mode == "sym_i8"
    s = lambda shape, dt: _spec(one_chip, shape, dt)

    def qd(x, qw, dlut, sx, zx, sw, zw, colsum, comp_r, comp_col, comp_mu):
        return ops.fused_qdot(x, qw, dlut, sx=sx,
                              zx=None if signed else zx, sw=sw,
                              zw=None if signed else zw,
                              colsum=None if signed else colsum,
                              comp_r=comp_r, comp_col=comp_col,
                              comp_mu=comp_mu, signed=signed,
                              compensate=True)

    table = platform.delta_table_dtype() or jnp.int16   # None: as built
    args = (s((M, K), jnp.float32), s((K, N), jnp.int32),
            s((256, 256), table),
            s((), jnp.float32), s((), jnp.float32),
            s((1, N), jnp.float32), s((1, N), jnp.float32),
            s((N,), jnp.float32), s((256,), jnp.float32),
            s((N,), jnp.float32), s((), jnp.float32))
    hlo = jax.jit(qd).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "onehot_qdot" in hlo
    assert " while(" not in hlo


@pytest.fixture(scope="module")
def smoke_serving_tree():
    """The smoke serving tree as the server prepares it (prequantized,
    calibrated, fused backend, merged projections), built on the CPU
    before any test steers the platform to its TPU choices."""
    from repro.launch import serve
    from repro.models import transformer as T
    args = serve.parse_args(["--smoke", "--calibrate", "1", "--requests",
                             "2", "--prompt-len", "1"])
    cfg, qcfg = configs.get_smoke("qwen3-1.7b"), serve.quant_config(args)
    params, _ = serve.prepare_params(
        T.init_params(jax.random.PRNGKey(0), cfg), cfg, qcfg, args)
    state = jax.eval_shape(
        lambda: T.init_decode_state(cfg, 2, 8, per_slot=True))
    return cfg, qcfg, params, state


def test_serve_step_scopes_survive_tpu_fusion(one_chip, on_tpu,
                                              smoke_serving_tree):
    """The smoke decode step compiled for the described v5e keeps each
    instruction's op_name through the TPU's fusion: every fusion, loop,
    dot and custom call resolves to a program scope (repro.obs), and the
    decode-attention kernel sits under the attention scope."""
    import re

    from repro import obs
    from repro.train import make_serve_step
    cfg, qcfg, params, state = smoke_serving_tree
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    hlo = jax.jit(make_serve_step(cfg, qcfg)).lower(
        place(params), place(state),
        _spec(one_chip, (2, 1), jnp.int32)).compile().as_text()
    table = obs.scopes_of_hlo(hlo)
    runs = re.compile(r" (fusion|while|dot|custom-call|convolution)\(")
    ops = {m.group(2): line for line in hlo.splitlines()
           if (m := obs._INSTR.match(line)) and runs.search(line)
           and m.group(2) in table}
    assert ops and all(table[n] != obs.UNSCOPED for n in ops), \
        [n for n in ops if table[n] == obs.UNSCOPED][:5]
    qdots = {"qdot.wqkv", "qdot.wo", "qdot.w_gateup", "qdot.w_down"}
    kernel = [n for n, line in ops.items() if "tpu_custom_call" in line]
    # the decode-attention kernel and the qdots' onehot_qdot kernels
    assert kernel and {table[n] for n in kernel} == {obs.ATTENTION} | qdots
    assert {obs.EMBED, obs.LAYERS, obs.ATTENTION, obs.FINAL_NORM,
            obs.UNEMBED, obs.SAMPLE} | qdots <= set(table.values())


# published widths by smoke width, for the qwen3-1.7b serving tree:
# d_model (and heads x head_dim), q|k|v, d_ff, gate|up, vocab, head_dim
WIDE = {64: CFG.d_model, 128: (CFG.n_heads + 2 * CFG.n_kv) * HD,
        192: CFG.d_ff, 384: 2 * CFG.d_ff, 512: CFG.vocab, 16: HD}


def test_serve_step_runs_one_kernel_per_qdot(one_chip, on_tpu,
                                             smoke_serving_tree):
    """The decode step at the benchmark cell's widths (qwen3-1.7b cut to
    two layers, four slots of 1,040 cache rows) compiled for the
    described v5e: each qdot.* scope holds one Pallas call per layer and
    no loop, where the blocked-XLA twin's K-block scans put 10,907 of a
    step's 11,282 device ops under qdot.*."""
    import dataclasses
    import re

    from repro import obs
    from repro.models import transformer as T
    from repro.train import make_serve_step
    smoke, qcfg, params, _ = smoke_serving_tree
    assert smoke.n_layers == 2
    cfg = dataclasses.replace(CFG, n_layers=smoke.n_layers)
    wide = jax.tree.map(lambda a: _spec(
        one_chip, tuple(WIDE.get(d, d) for d in a.shape), a.dtype), params)
    assert wide["units"][0]["mlp"]["w_gateup"].q.shape == (
        2, CFG.d_model, 2 * CFG.d_ff)
    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(lambda: T.init_decode_state(
                             cfg, 4, 1040, per_slot=True)))
    hlo = jax.jit(make_serve_step(cfg, qcfg)).lower(
        wide, state, _spec(one_chip, (4, 1), jnp.int32)).compile().as_text()
    table = obs.scopes_of_hlo(hlo)
    per_scope = {}
    for line in hlo.splitlines():
        m = obs._INSTR.match(line)
        if m and m.group(2) in table:
            op = re.search(r" ([a-z][a-z\-]*)\(", line[m.end():])
            kind = ("kernel" if "tpu_custom_call" in line
                    else op.group(1) if op else "")
            per_scope.setdefault(table[m.group(2)], []).append(kind)
    for q in ("qdot.wqkv", "qdot.wo", "qdot.w_gateup", "qdot.w_down"):
        assert per_scope[q].count("kernel") == cfg.n_layers, q
        # the kernel is the projection's only matmul: no loop, and no
        # row selection or lookup left to XLA
        assert not {"while", "dot", "convolution", "gather"} & set(
            per_scope[q]), q
    assert per_scope[obs.ATTENTION].count("kernel") == cfg.n_layers
