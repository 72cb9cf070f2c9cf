"""Qwen3 dense decoder (``model_type`` ``qwen3``): its parameter tree, the
program's configuration and served tree, its projections, and its plain
reference forward.

Semantics written down here, as the configuration states them:

* pre-norm blocks: x + attn(RMSNorm(x)), then x + mlp(RMSNorm(x)), with
  RMSNorm x / sqrt(mean(x^2) + eps) * g;
* attention: q, k and v projections (grouped-query: each key-value head
  serves a group of query heads), qk-norm (RMSNorm over each head's
  dims), full rotary (rotate-half over every dim of a head, base
  ``rope_theta``), then ``reference._attention``;
* MLP: SwiGLU, silu(x W_gate) * (x W_up), then W_down;
* the final RMSNorm, then the unembedding: the tied embedding in
  float32, exact.

Weights: the embedding (tied head), the final norm gain, and one stacked
unit of layers, each leaf with a leading layer axis.  Dense weights are
N(0, 1) / sqrt(fan-in), the embedding N(0, 1) * 0.02, norm gains
1 + 0.1 N(0, 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference


def _check(cfg: dict) -> None:
    """The forms this module serves: refuse a file that states others."""
    if (cfg["hidden_act"], cfg["qk_norm"],
            cfg["tie_word_embeddings"]) != ("silu", True, True):
        raise ValueError(f"{cfg['name']}: qwen3 is SwiGLU with qk-norm and a "
                         f"tied head; the file states hidden_act "
                         f"{cfg['hidden_act']!r}, qk_norm {cfg['qk_norm']}, "
                         f"tie_word_embeddings {cfg['tie_word_embeddings']}")


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the float32 parameter tree, for the configuration
    file's sizes."""
    _check(cfg)
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    attn = {"wq": (L, d, h * hd), "wk": (L, d, kv * hd),
            "wv": (L, d, kv * hd), "wo": (L, h * hd, d),
            "q_norm": (L, hd), "k_norm": (L, hd)}
    mlp = {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
    return {"embed": (v, d), "final_norm": (d,),
            "units": [{"norm1": (L, d), "attn": attn, "norm2": (L, d),
                       "mlp": mlp}]}


def leaf(key, names, shape):
    """Initial values of the leaf at ``names`` (its path of dict keys)."""
    name = names[-1]
    if name == "embed":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    if name.startswith("w"):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(shape[-2]))
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def program_config(cfg: dict):
    """The program's ArchConfig for a configuration file."""
    _check(cfg)
    from repro.models.transformer import ArchConfig
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg["head_dim"],
        mlp_kind="swiglu", qk_norm=True, rope_theta=float(cfg["rope_theta"]),
        max_seq=cfg["max_position_embeddings"])


# merged wrappers (the program's fuse_projections) and their members, in
# column order
GROUPS = {"wqkv": ("wq", "wk", "wv"), "w_gateup": ("w_gate", "w_up")}


def projections(cfg: dict) -> list:
    """One layer's serving projections as the decode step calls them:
    (name, members, K, N), members the (group, leaf) merged into it."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return [("wqkv", tuple(("attn", n) for n in GROUPS["wqkv"]), d,
             (h + 2 * kv) * hd),
            ("wo", (("attn", "wo"),), h * hd, d),
            ("w_gateup", tuple(("mlp", n) for n in GROUPS["w_gateup"]), d,
             2 * f),
            ("w_down", (("mlp", "w_down"),), f, d)]


def layer_macs(cfg: dict) -> int:
    """Multiply-accumulates of one layer's projections for one token."""
    return sum(K * N for _, _, K, N in projections(cfg))


def head_bytes(cfg: dict) -> int:
    """Bytes of one element of the output head's table: the tied
    embedding, float32 as ``leaf`` makes it."""
    return 4


def prepared_spec(cfg: dict, qcfg) -> dict:
    """Shapes of the tree ``prepare_params`` serves from: per-tensor
    uint8 weights prequantized, static activation scales, cached
    compensation column sums, q|k|v and gate|up merged (per-column
    scales)."""
    _check(cfg)
    from repro.quant.linear import QuantizedWeight
    f32 = jnp.float32
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    hd, V = cfg["head_dim"], cfg["vocab_size"]
    S = jax.ShapeDtypeStruct

    def qw(path, K, N, merged):
        c = (L, 1, N) if merged else (L, 1, 1)
        return QuantizedWeight(
            None, S((L, K, N), jnp.int32), S(c, f32), S(c, f32),
            colsum=S((L, 1, N), f32), act_scale=S((L,), f32),
            act_zp=S((L,), f32), comp_col=S((L, 1, N), f32),
            mode=qcfg.mode, path=path, per_channel=merged,
            merged=merged)
    unit = {"attn": {"q_norm": S((L, hd), f32), "k_norm": S((L, hd), f32)},
            "mlp": {}, "norm1": S((L, d), f32), "norm2": S((L, d), f32)}
    for name, members, K, N in projections(cfg):
        group = members[0][0]
        unit[group][name] = qw(f"units.0.{group}.{name}", K, N,
                               len(members) > 1)
    return {"embed": S((V, d), f32), "final_norm": S((d,), f32),
            "units": [unit]}


def rmsnorm(x, g, eps: float):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def rope(x, pos, theta: float):
    """Rotate-half rotary over every dim of a head.  x: (T, H, D); pos:
    (T,) positions."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs[None, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


class Model(reference.Decoder):
    """The reference forward of a qwen3 configuration at one grid."""

    def __init__(self, cfg: dict, weights: dict, bits: int = 8):
        _check(cfg)
        super().__init__(cfg, bits)
        self.h = cfg["num_attention_heads"]
        self.kv = cfg["num_key_value_heads"]
        self.hd = cfg["head_dim"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.embed = weights["embed"]
        self.final_norm = weights["final_norm"]
        unit = weights["units"][0]
        self.layers = []
        for l in range(cfg["num_hidden_layers"]):
            lw = jax.tree.map(lambda a: a[l], unit)
            prep = {}
            for k, w in [*lw["attn"].items(), *lw["mlp"].items(),
                         ("norm1", lw["norm1"]), ("norm2", lw["norm2"])]:
                prep[k] = (reference.quantize_weight(w, self.grid)
                           if k.startswith("w") else w)
            self.layers.append(prep)

    def _layer(self, p, x, pos, attend, act_of):
        def lin(site, w, v):
            return self.linear(p[w], v, act_of(site, v))
        T = x.shape[0]
        h = rmsnorm(x, p["norm1"], self.eps)
        q = lin("qkv", "wq", h).reshape(T, self.h, self.hd)
        k = lin("qkv", "wk", h).reshape(T, self.kv, self.hd)
        v = lin("qkv", "wv", h).reshape(T, self.kv, self.hd)
        q = rmsnorm(q, p["q_norm"], self.eps)
        k = rmsnorm(k, p["k_norm"], self.eps)
        q = rope(q, pos, self.theta)
        k = rope(k, pos, self.theta)
        o = attend(q, k, v)
        x = x + lin("o", "wo", o.reshape(T, self.h * self.hd))
        h2 = rmsnorm(x, p["norm2"], self.eps)
        hh = jax.nn.silu(lin("up", "w_gate", h2)) * lin("up", "w_up", h2)
        return x + lin("down", "w_down", hh)

    def _logits(self, x):
        x = rmsnorm(x, self.final_norm, self.eps)
        return jnp.matmul(x, self.embed.T, precision=reference.HIGHEST)
