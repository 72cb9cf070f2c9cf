"""Shared neural layers (pure-functional, params = nested dicts).

Every dense projection routes through quant.qdot, i.e. through the
paper's approximate multiplier when the run's QuantConfig enables it.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.quant import QuantConfig, qdot
from .sharding import constrain


def dense_init(rng, in_dim: int, out_dim: int, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * scale)


def rmsnorm_init(dim: int):
    return jnp.ones((dim,), jnp.float32)


def rmsnorm(x, gamma, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)) * gamma


def rope(x, positions, theta: float = 10000.0):
    """Rotary embedding. x: (B, S, H, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.asarray(positions, jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]                       # (1, S)
    ang = pos[:, :, None, None] * freqs[None, None, None, :]  # (B,S,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# Attention (GQA/MQA, optional sliding window, qk_norm, KV cache)
# ---------------------------------------------------------------------------

def attention_init(rng, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qk_norm: bool = False):
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model),
    }
    if qk_norm:
        p["q_norm"] = rmsnorm_init(head_dim)
        p["k_norm"] = rmsnorm_init(head_dim)
    return p


def _split_heads(x, n, d):
    return x.reshape(*x.shape[:-1], n, d)


def attention(p, x, positions, qcfg: QuantConfig, *, n_heads: int, n_kv: int,
              head_dim: int, causal: bool = True, window: Optional[int] = None,
              qk_norm: bool = False, cache: Optional[dict] = None,
              cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              rope_theta: float = 10000.0):
    """x: (B, S, D). Returns (out, new_cache).

    cache: {"k": (B, S_max, n_kv, hd), "v": ..., "idx": int32} for decode.
    cross_kv: precomputed (k, v) for encoder-decoder cross attention.
    """
    B, S, _ = x.shape
    idx = cache["idx"] if cache is not None else None
    per_slot = idx is not None and idx.ndim == 1
    if positions is None and cache is not None:
        positions = (idx[:, None] + jnp.arange(S)) if per_slot \
            else (idx + jnp.arange(S))
    if cross_kv is None and "wqkv" in p:
        # serving-time merged projection (quant.linear.fuse_projections):
        # one qdot, split by head counts — per-column outputs are
        # bit-identical to the three separate calls
        qkv = qdot(x, p["wqkv"], qcfg)
        q, k, v = jnp.split(
            qkv, [n_heads * head_dim, (n_heads + n_kv) * head_dim], axis=-1)
        q = _split_heads(q, n_heads, head_dim)
        k = _split_heads(k, n_kv, head_dim)
        v = _split_heads(v, n_kv, head_dim)
    else:
        q = _split_heads(qdot(x, p["wq"], qcfg), n_heads, head_dim)
        if cross_kv is None:
            k = _split_heads(qdot(x, p["wk"], qcfg), n_kv, head_dim)
            v = _split_heads(qdot(x, p["wv"], qcfg), n_kv, head_dim)
        else:
            k, v = cross_kv

    with obs.scope(obs.ATTENTION, q):
        out, new_cache = _attend(
            p, q, k, v, positions, cache, n_heads=n_heads, n_kv=n_kv,
            head_dim=head_dim, causal=causal, window=window,
            qk_norm=qk_norm, cross_kv=cross_kv, rope_theta=rope_theta)
    return qdot(out, p["wo"], qcfg), new_cache


def _attend(p, q, k, v, positions, cache, *, n_heads: int, n_kv: int,
            head_dim: int, causal: bool, window: Optional[int],
            qk_norm: bool, cross_kv, rope_theta: float):
    """Everything of ``attention`` between its projections: q (B, S, H,
    hd), k and v (B, S_kv, n_kv, hd) -> (out (B, S, H * hd), new_cache).
    ``positions`` are the queries' (attention() fills them in from the
    cache's idx)."""
    B, S = q.shape[:2]
    idx = cache["idx"] if cache is not None else None
    per_slot = idx is not None and idx.ndim == 1
    if cache is not None and S == 1 and cross_kv is None:
        # fused decode step: qk-norm + rope + cache append + masked
        # single-query attention in one lowered body (Pallas on TPU,
        # bit-matched XLA twin elsewhere) — kernels.ops.decode_attention
        from repro.kernels import ops as kops
        out, ck, cv = kops.decode_attention(
            q, k, v, cache["k"], cache["v"], idx, n_heads=n_heads,
            n_kv=n_kv, head_dim=head_dim,
            rope_theta=rope_theta if rope_theta else 0.0, window=window,
            q_gain=p.get("q_norm") if qk_norm else None,
            k_gain=p.get("k_norm") if qk_norm else None)
        return out, {"k": ck, "v": cv, "idx": idx + S}

    if qk_norm:
        q = rmsnorm(q, p["q_norm"])
        if cross_kv is None:
            k = rmsnorm(k, p["k_norm"])
    if cross_kv is None and rope_theta:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    if cache is None:  # training/prefill; decode layouts follow the cache
        q = constrain(q, "batch", None, "heads", None)
        k = constrain(k, "batch", None, "kv", None)

    new_cache = None
    if cache is not None:
        if per_slot:
            upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
                c, n, (i, 0, 0)))
            ck = upd(cache["k"], k.astype(cache["k"].dtype), idx)
            cv = upd(cache["v"], v.astype(cache["v"].dtype), idx)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
        new_cache = {"k": ck, "v": cv, "idx": idx + S}
        k, v = ck, cv

    S_k = k.shape[1]
    group = n_heads // max(n_kv, 1)
    qg = q.reshape(B, S, n_kv, group, head_dim)

    if cache is not None:
        qpos = positions                      # (S,) or per-slot (B, S)
        kv_limit = idx + S
    elif positions is None:  # non-causal cross attention: mask is all-ones
        qpos = jnp.arange(S)
        kv_limit = None
    else:
        qpos = positions if positions.ndim == 1 else positions[0]
        kv_limit = None

    def attend(q_blk, qpos_blk):
        """q_blk: (B, sq, n_kv, group, hd) -> (B, sq, n_kv, group, hd).

        Memory-bounded attention: logits only ever materialize for one
        query block (sq x S_k), never the full S x S_k surface."""
        lg = jnp.einsum("bsngd,btnd->bngst", q_blk, k) / math.sqrt(head_dim)
        kpos = jnp.arange(S_k)
        if qpos_blk is not None and qpos_blk.ndim == 2:
            # per-slot cache positions: mask varies over the batch
            m = (kpos[None, None, :] <= qpos_blk[:, :, None]) & \
                (kpos[None, None, :] < kv_limit[:, None, None])
            if window is not None:
                m = m & (kpos[None, None, :] > qpos_blk[:, :, None] - window)
            mb = m[:, None, None]             # (B, 1, 1, sq, S_k)
        else:
            if kv_limit is not None:
                m = (kpos[None, :] <= qpos_blk[:, None]) & \
                    (kpos[None, :] < kv_limit)
            elif causal:
                m = kpos[None, :] <= qpos_blk[:, None]
            else:
                m = jnp.ones((q_blk.shape[1], S_k), bool)
            if window is not None:
                m = m & (kpos[None, :] > qpos_blk[:, None] - window)
            mb = m[None, None, None]
        lg = jnp.where(mb, lg, -1e30)
        pr = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
        return jnp.einsum("bngst,btnd->bsngd", pr, v)

    CHUNK = 512
    if S > CHUNK and S % CHUNK == 0 and qpos.ndim == 1:
        n_blk = S // CHUNK
        qb = qg.reshape(B, n_blk, CHUNK, n_kv, group, head_dim)
        qb = jnp.moveaxis(qb, 1, 0)              # (n_blk, B, CHUNK, ...)
        pb = qpos.reshape(n_blk, CHUNK)
        ob = jax.lax.map(lambda args: attend(*args), (qb, pb))
        out = jnp.moveaxis(ob, 0, 1).reshape(B, S, n_kv, group, head_dim)
    else:
        out = attend(qg, qpos)
    return out.reshape(B, S, n_heads * head_dim), new_cache


def make_cache(batch: int, s_max: int, n_kv: int, head_dim: int,
               dtype=jnp.bfloat16, per_slot: bool = False):
    """KV cache. ``per_slot=True`` gives each batch slot its own cache
    position (idx (B,) instead of scalar) — batched multi-slot decode,
    where the continuous-batching driver keeps requests at different
    depths in the same step."""
    idx = (jnp.zeros((batch,), jnp.int32) if per_slot
           else jnp.zeros((), jnp.int32))
    return {"k": jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
            "v": jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
            "idx": idx}


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def mlp_init(rng, d_model: int, d_ff: int, kind: str):
    ks = jax.random.split(rng, 3)
    if kind in ("geglu", "swiglu"):
        return {"w_gate": dense_init(ks[0], d_model, d_ff),
                "w_up": dense_init(ks[1], d_model, d_ff),
                "w_down": dense_init(ks[2], d_ff, d_model)}
    return {"w_up": dense_init(ks[0], d_model, d_ff),
            "w_down": dense_init(ks[1], d_ff, d_model)}


def mlp(p, x, qcfg: QuantConfig, kind: str):
    if kind in ("geglu", "swiglu") and "w_gateup" in p:
        # merged gate|up projection (quant.linear.fuse_projections):
        # one qdot, split down the middle — bit-identical per column
        act = jax.nn.gelu if kind == "geglu" else jax.nn.silu
        gu = qdot(x, p["w_gateup"], qcfg)
        g, u = jnp.split(gu, 2, axis=-1)
        h = act(g) * u
    elif kind == "geglu":
        h = jax.nn.gelu(qdot(x, p["w_gate"], qcfg)) * qdot(x, p["w_up"], qcfg)
    elif kind == "swiglu":
        h = jax.nn.silu(qdot(x, p["w_gate"], qcfg)) * qdot(x, p["w_up"], qcfg)
    elif kind == "relu2":  # nemotron squared-ReLU
        h = jnp.square(jax.nn.relu(qdot(x, p["w_up"], qcfg)))
    else:  # gelu
        h = jax.nn.gelu(qdot(x, p["w_up"], qcfg))
    h = constrain(h, "batch", None, "ffn")
    return qdot(h, p["w_down"], qcfg)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_init(rng, vocab: int, d_model: int):
    return jax.random.normal(rng, (vocab, d_model), jnp.float32) * 0.02


def embed(table, tokens):
    return constrain(jnp.take(table, tokens, axis=0), "batch", None, "embed")


def unembed(table, x, qcfg: QuantConfig):
    """Tied output head.  Exact by default (QuantConfig.quant_unembed);
    routing it through the approximate multiplier is supported but
    memory-hostile at 256k vocabs (see EXPERIMENTS.md §Perf)."""
    with obs.scope(obs.UNEMBED, x):
        if not qcfg.quant_unembed:
            return jnp.matmul(x, table.T)
        return qdot(x, table.T, qcfg)
