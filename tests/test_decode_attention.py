"""Fused decode-step attention/cache op: the XLA twin must reproduce the
generic attention path's decode math bit for bit, and the Pallas kernel
(interpret mode off-TPU) must agree with the twin through every feature
combination (qk-norm, rope, sliding window, per-slot positions, cache
tiling)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.attention import decode_attention_step

B, H, KV, HD, S = 3, 4, 2, 16, 48


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


def _inputs(rng, per_slot=False, cache_dtype=jnp.bfloat16):
    q = _rand(rng, B, 1, H, HD)
    k = _rand(rng, B, 1, KV, HD)
    v = _rand(rng, B, 1, KV, HD)
    kc = _rand(rng, B, S, KV, HD).astype(cache_dtype)
    vc = _rand(rng, B, S, KV, HD).astype(cache_dtype)
    idx = (jnp.asarray(rng.integers(0, S - 1, (B,)), jnp.int32)
           if per_slot else jnp.int32(rng.integers(0, S - 1)))
    return q, k, v, kc, vc, idx


def _oracle(q, k, v, kc, vc, idx, *, window=None, q_gain=None,
            k_gain=None, rope_theta=10000.0):
    """The pre-kernel decode op sequence of models.layers.attention
    (qk-norm -> rope -> cache append -> masked GQA attention), inlined
    as an independent oracle."""
    def rmsnorm(x, g, eps=1e-6):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                       keepdims=True)
        return (x * jax.lax.rsqrt(var + eps)) * g

    def rope(x, pos, theta):
        d = x.shape[-1]
        half = d // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        p = jnp.asarray(pos, jnp.float32)
        if p.ndim == 1:
            p = p[None, :]
        ang = p[:, :, None, None] * freqs[None, None, None, :]
        c, s = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    positions = idx + jnp.arange(1)
    if q_gain is not None:
        q = rmsnorm(q, q_gain)
        k = rmsnorm(k, k_gain)
    if rope_theta:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    ck = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                      (0, idx, 0, 0))
    cv = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                      (0, idx, 0, 0))
    group = H // KV
    qg = q.reshape(B, 1, KV, group, HD)
    lg = jnp.einsum("bsngd,btnd->bngst", qg, ck) / math.sqrt(HD)
    kpos = jnp.arange(S)
    m = (kpos[None, :] <= positions[:, None]) & (kpos[None, :] < idx + 1)
    if window is not None:
        m = m & (kpos[None, :] > positions[:, None] - window)
    lg = jnp.where(m[None, None, None], lg, -1e30)
    pr = jax.nn.softmax(lg.astype(jnp.float32), -1)
    out = jnp.einsum("bngst,btnd->bsngd", pr, cv)
    return out.reshape(B, 1, H * HD), ck, cv


@pytest.mark.parametrize("qk_norm,theta,window", [
    (False, 10000.0, None),
    (True, 10000.0, None),
    (False, 500.0, 6),
    (True, 0.0, None),
])
def test_twin_bit_identical_to_generic_path(qk_norm, theta, window):
    rng = np.random.default_rng(0)
    q, k, v, kc, vc, idx = _inputs(rng)
    qg = _rand(rng, HD) if qk_norm else None
    kg = _rand(rng, HD) if qk_norm else None
    o_ref, ck_ref, cv_ref = _oracle(q, k, v, kc, vc, idx, window=window,
                                    q_gain=qg, k_gain=kg,
                                    rope_theta=theta)
    o, ck, cv = ref.decode_attention_ref(
        q, k, v, kc, vc, idx, n_heads=H, n_kv=KV, head_dim=HD,
        rope_theta=theta, window=window, q_gain=qg, k_gain=kg)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(ck_ref))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(cv_ref))


@pytest.mark.parametrize("per_slot", [False, True])
@pytest.mark.parametrize("qk_norm,theta,window,block_s", [
    (True, 10000.0, None, 128),
    (True, 10000.0, None, 16),     # multi-tile online softmax
    (True, 10000.0, None, 32),     # ragged last tile (48 = 32 + 16 rows)
    (False, 500.0, 6, 16),
    (False, 0.0, None, 128),
])
def test_pallas_kernel_matches_twin(per_slot, qk_norm, theta, window,
                                    block_s):
    """Pallas lowering (interpret off-TPU) vs the XLA twin: caches are
    bit-exact (same roped rows through the cache dtype); the attention
    output agrees to f32 ULPs (online vs two-pass softmax)."""
    rng = np.random.default_rng(1)
    q, k, v, kc, vc, idx = _inputs(rng, per_slot=per_slot)
    qg = _rand(rng, HD) if qk_norm else None
    kg = _rand(rng, HD) if qk_norm else None
    kw = dict(n_heads=H, n_kv=KV, head_dim=HD, rope_theta=theta,
              window=window, q_gain=qg, k_gain=kg)
    o_t, ck_t, cv_t = ops.decode_attention(q, k, v, kc, vc, idx,
                                           lowering="xla", **kw)
    o_p, ck_p, cv_p = ops.decode_attention(q, k, v, kc, vc, idx,
                                           lowering="pallas",
                                           block_s=block_s, **kw)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_t),
                               rtol=0, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(ck_p), np.asarray(ck_t))
    np.testing.assert_array_equal(np.asarray(cv_p), np.asarray(cv_t))


def test_ragged_last_tile_matches_twin():
    """Positions inside a ragged last cache tile (S = 48 over 32-row
    tiles): the rows past S that the tile covers hold no cache data and
    must not reach the output."""
    rng = np.random.default_rng(5)
    q, k, v, kc, vc, _ = _inputs(rng, per_slot=True)
    idx = jnp.asarray([S - 1, 32, 40], jnp.int32)
    kw = dict(n_heads=H, n_kv=KV, head_dim=HD)
    o_t, ck_t, _ = ops.decode_attention(q, k, v, kc, vc, idx,
                                        lowering="xla", **kw)
    o_p, ck_p, _ = ops.decode_attention(q, k, v, kc, vc, idx,
                                        lowering="pallas", block_s=32, **kw)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_t),
                               rtol=0, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(ck_p), np.asarray(ck_t))


def test_per_slot_positions_match_per_request_runs():
    """A batch with per-slot cache positions must equal running each
    slot alone at its own scalar position (the multi-slot decode
    invariant the continuous-batching driver relies on)."""
    rng = np.random.default_rng(2)
    q, k, v, kc, vc, _ = _inputs(rng, per_slot=True)
    idx = jnp.asarray([0, 7, S - 2], jnp.int32)
    o_b, ck_b, cv_b = ref.decode_attention_ref(
        q, k, v, kc, vc, idx, n_heads=H, n_kv=KV, head_dim=HD)
    for b in range(B):
        o_1, ck_1, cv_1 = ref.decode_attention_ref(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], kc[b:b + 1],
            vc[b:b + 1], idx[b], n_heads=H, n_kv=KV, head_dim=HD)
        np.testing.assert_array_equal(np.asarray(o_b[b]),
                                      np.asarray(o_1[0]))
        np.testing.assert_array_equal(np.asarray(ck_b[b]),
                                      np.asarray(ck_1[0]))
        np.testing.assert_array_equal(np.asarray(cv_b[b]),
                                      np.asarray(cv_1[0]))


def test_kernel_appends_through_cache_dtype():
    """The appended row must be read back through the cache dtype (the
    bf16 round trip the unfused path has), not kept in f32."""
    rng = np.random.default_rng(3)
    q, k, v, kc, vc, idx = _inputs(rng)
    _, ck, _ = ref.decode_attention_ref(
        q, k, v, kc, vc, idx, n_heads=H, n_kv=KV, head_dim=HD,
        rope_theta=0.0)
    row = np.asarray(ck)[:, int(idx)]
    np.testing.assert_array_equal(
        row, np.asarray(k.astype(jnp.bfloat16))[:, 0])


def test_kernel_raw_entry_shapes():
    rng = np.random.default_rng(4)
    q, k, v, kc, vc, _ = _inputs(rng)
    pos = jnp.full((B,), 5, jnp.int32)
    out = decode_attention_step(q.reshape(B, H, HD), kc, vc, pos,
                                group=H // KV, block_s=16)
    assert out.shape == (B, H, HD) and out.dtype == jnp.float32
    assert np.isfinite(np.asarray(out)).all()
