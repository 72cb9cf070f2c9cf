"""Unified model facade for all assigned architectures.

One ArchConfig describes any of the ten architectures; layers are grouped
into repeated *pattern units* and applied with jax.lax.scan over stacked
per-unit parameters (compile-time O(1) in depth — essential for the
96-layer dry-runs).  Heterogeneous patterns (hybrid 1:2, xLSTM m:s) stay
faithfully interleaved because the scan unit IS the pattern.

API:
  init_params(rng, cfg)                     -> params pytree
  forward_train(params, batch, cfg, qcfg)   -> (loss, metrics)
  forward_decode(params, state, tok, cfg, qcfg) -> (logits, state)
  init_decode_state(cfg, batch, s_max)      -> state pytree
  input_specs(cfg, shape)                   -> ShapeDtypeStructs (launch/)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.calib.observe import pscan
from repro.quant import QuantConfig
from . import layers, moe as moe_mod, recurrent
from .sharding import constrain


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    mlp_kind: str = "swiglu"
    qk_norm: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None          # sliding-window size (None = full)
    # moe
    n_experts: int = 0
    top_k: int = 0
    shared_expert_ff: int = 0
    # recurrent / hybrid
    pattern: Tuple[str, ...] = ("attn",)  # unit, e.g. ("rec","rec","attn")
    d_rnn: int = 0
    # encoder-decoder (whisper)
    enc_layers: int = 0
    enc_seq: int = 0
    frontend_dim: int = 0
    # vlm
    n_prefix: int = 0
    # capacity
    max_seq: int = 32768
    sub_quadratic: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_units(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, \
            (self.name, self.n_layers, self.pattern)
        return self.n_layers // len(self.pattern)

    def param_count(self) -> int:
        """Approximate parameter count (for 6ND roofline math)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        att = d * self.n_heads * self.hd + 2 * d * self.n_kv * self.hd \
            + self.n_heads * self.hd * d
        glu = self.mlp_kind in ("geglu", "swiglu")
        mlp = d * f * (3 if glu else 2)
        per_layer = 0.0
        for kind in self.pattern:
            if kind == "attn":
                per_layer += att + (mlp if f else 0)
            elif kind == "moe":
                per_layer += att + self.n_experts * mlp \
                    + (d * self.shared_expert_ff * 3 if self.shared_expert_ff else 0)
            elif kind == "rec":
                per_layer += 3 * d * self.d_rnn + self.d_rnn * d + (mlp if f else 0)
            elif kind in ("mlstm", "slstm"):
                per_layer += (4 * d * d) if kind == "mlstm" else (5 * d * d)
        total = per_layer / len(self.pattern) * self.n_layers + v * d
        if self.enc_layers:
            total += self.enc_layers * (att + mlp) + att * self.enc_layers  # cross
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.family != "moe":
            return self.param_count()
        d, f = self.d_model, self.d_ff
        att = d * self.n_heads * self.hd + 2 * d * self.n_kv * self.hd \
            + self.n_heads * self.hd * d
        glu = self.mlp_kind in ("geglu", "swiglu")
        mlp = d * f * (3 if glu else 2)
        per_layer = att + self.top_k * mlp + (
            d * self.shared_expert_ff * 3 if self.shared_expert_ff else 0)
        return int(per_layer * self.n_layers + self.vocab * d)


# ---------------------------------------------------------------------------
# Per-kind block init/apply
# ---------------------------------------------------------------------------

def _block_init(rng, cfg: ArchConfig, kind: str):
    ks = jax.random.split(rng, 4)
    p = {"norm1": layers.rmsnorm_init(cfg.d_model)}
    if kind == "attn":
        p["attn"] = layers.attention_init(ks[0], cfg.d_model, cfg.n_heads,
                                          cfg.n_kv, cfg.hd, cfg.qk_norm)
        if cfg.d_ff:
            p["norm2"] = layers.rmsnorm_init(cfg.d_model)
            p["mlp"] = layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    elif kind == "moe":
        p["attn"] = layers.attention_init(ks[0], cfg.d_model, cfg.n_heads,
                                          cfg.n_kv, cfg.hd, cfg.qk_norm)
        p["norm2"] = layers.rmsnorm_init(cfg.d_model)
        p["moe"] = moe_mod.moe_init(ks[1], cfg.d_model, cfg.d_ff,
                                    cfg.n_experts, cfg.mlp_kind,
                                    cfg.shared_expert_ff)
    elif kind == "rec":
        p["rec"] = recurrent.rglru_init(ks[0], cfg.d_model, cfg.d_rnn)
        if cfg.d_ff:
            p["norm2"] = layers.rmsnorm_init(cfg.d_model)
            p["mlp"] = layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    elif kind == "mlstm":
        p["mlstm"] = recurrent.mlstm_init(ks[0], cfg.d_model, cfg.n_heads)
    elif kind == "slstm":
        p["slstm"] = recurrent.slstm_init(ks[0], cfg.d_model)
    else:
        raise ValueError(kind)
    return p


def _block_apply(p, x, positions, cfg: ArchConfig, qcfg: QuantConfig,
                 kind: str, cache=None, window=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.rmsnorm(x, p["norm1"])
    if kind in ("attn", "moe"):
        att, new_cache = layers.attention(
            p["attn"], h, positions, qcfg, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.hd, causal=True, window=window, qk_norm=cfg.qk_norm,
            cache=cache, rope_theta=cfg.rope_theta)
        x = x + att
        if "norm2" in p:
            h2 = layers.rmsnorm(x, p["norm2"])
            if kind == "moe":
                y, aux = moe_mod.moe(p["moe"], h2, qcfg,
                                     n_experts=cfg.n_experts, top_k=cfg.top_k,
                                     kind=cfg.mlp_kind,
                                     shared=bool(cfg.shared_expert_ff))
            else:
                y = layers.mlp(p["mlp"], h2, qcfg, cfg.mlp_kind)
            x = x + y
    elif kind == "rec":
        y, new_cache = recurrent.rglru(p["rec"], h, qcfg, state=cache)
        x = x + y
        if "norm2" in p:
            x = x + layers.mlp(p["mlp"], layers.rmsnorm(x, p["norm2"]), qcfg,
                               cfg.mlp_kind)
    elif kind == "mlstm":
        y, new_cache = recurrent.mlstm(p["mlstm"], h, qcfg, cfg.n_heads,
                                       state=cache)
        x = x + y
    elif kind == "slstm":
        y, new_cache = recurrent.slstm(p["slstm"], h, qcfg, state=cache)
        x = x + y
    else:
        raise ValueError(kind)
    return x, new_cache, aux


def _kind_window(cfg: ArchConfig, kind: str, pos_in_unit: int):
    """Sliding window policy: 'attn' in hybrids = local attention."""
    if cfg.family == "hybrid" and kind == "attn":
        return cfg.window or 2048
    return cfg.window


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def init_params(rng, cfg: ArchConfig) -> Dict:
    r_embed, r_units, r_enc = jax.random.split(rng, 3)
    params: Dict = {"embed": layers.embed_init(r_embed, cfg.vocab, cfg.d_model),
                    "final_norm": layers.rmsnorm_init(cfg.d_model)}
    # stacked pattern units: for each slot in the unit, stack n_units params
    unit_params = []
    for slot, kind in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(r_units, slot), cfg.n_units)
        stacked = jax.vmap(lambda k: _block_init(k, cfg, kind))(keys)
        unit_params.append(stacked)
    params["units"] = unit_params
    if cfg.family == "encdec":
        params["enc"] = _init_encoder(r_enc, cfg)
    if cfg.frontend_dim and cfg.frontend_dim != cfg.d_model:
        params["frontend_proj"] = layers.dense_init(
            jax.random.fold_in(rng, 7), cfg.frontend_dim, cfg.d_model)
    return params


def _init_encoder(rng, cfg: ArchConfig) -> Dict:
    def one(k):
        ks = jax.random.split(k, 3)
        return {
            "norm1": layers.rmsnorm_init(cfg.d_model),
            "attn": layers.attention_init(ks[0], cfg.d_model, cfg.n_heads,
                                          cfg.n_kv, cfg.hd),
            "norm2": layers.rmsnorm_init(cfg.d_model),
            "mlp": layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_kind),
        }
    keys = jax.random.split(rng, cfg.enc_layers)
    enc = {"layers": jax.vmap(one)(keys),
           "norm": layers.rmsnorm_init(cfg.d_model)}
    # decoder cross-attention params (stacked over ALL decoder layers)
    keys2 = jax.random.split(jax.random.fold_in(rng, 1), cfg.n_layers)
    enc["cross"] = jax.vmap(
        lambda k: {"norm": layers.rmsnorm_init(cfg.d_model),
                   "attn": layers.attention_init(k, cfg.d_model, cfg.n_heads,
                                                 cfg.n_kv, cfg.hd)})(keys2)
    return enc


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _run_encoder(params, frontend, cfg: ArchConfig, qcfg: QuantConfig):
    """frontend: (B, S_enc, frontend_dim or d_model) precomputed embeddings
    (the modality STUB per the assignment)."""
    from repro.quant import qdot
    x = frontend
    if "frontend_proj" in params:
        x = qdot(x, params["frontend_proj"], qcfg)
    enc = params["enc"]
    pos = jnp.arange(x.shape[1])

    def body(x, lp):
        h = layers.rmsnorm(x, lp["norm1"])
        att, _ = layers.attention(lp["attn"], h, pos, qcfg,
                                  n_heads=cfg.n_heads, n_kv=cfg.n_kv,
                                  head_dim=cfg.hd, causal=False)
        x = x + att
        x = x + layers.mlp(lp["mlp"], layers.rmsnorm(x, lp["norm2"]), qcfg,
                           cfg.mlp_kind)
        return x, None

    # pscan == jax.lax.scan unless a calibration observer is active
    # (repro.calib unrolls the layer stacks to name per-layer sites)
    x, _ = pscan(body, x, enc["layers"])
    return layers.rmsnorm(x, enc["norm"])


def _decoder_stack(params, x, positions, cfg: ArchConfig, qcfg: QuantConfig,
                   caches=None, cross_ctx=None):
    """Scan the pattern units. caches: list per slot of stacked (n_units,...)
    cache trees (or None). cross_ctx: encoder output (B, S_enc, D) for
    enc-dec models. Returns (x, new_caches, aux_total)."""
    from repro.quant import qdot
    new_caches = []
    aux_total = jnp.zeros((), jnp.float32)

    for slot, kind in enumerate(cfg.pattern):
        slot_params = params["units"][slot]
        slot_cache = caches[slot] if caches is not None else None
        window = _kind_window(cfg, kind, slot)
        has_cross = cross_ctx is not None and kind == "attn" \
            and cfg.family == "encdec"
        cross_params = params["enc"]["cross"] if has_cross else None

        def body(carry, inp):
            x, aux = carry
            if has_cross and slot_cache is not None:
                lp, cache_l, xp = inp
            elif has_cross:
                lp, xp = inp
                cache_l = None
            elif slot_cache is not None:
                lp, cache_l = inp
                xp = None
            else:
                lp, cache_l, xp = inp, None, None
            x = constrain(x, "batch", "seq_shard", None)
            x, nc, a = _block_apply(lp, x, positions, cfg, qcfg, kind,
                                    cache=cache_l, window=window)
            x = constrain(x, "batch", "seq_shard", None)  # carry stays sharded
            if xp is not None:
                hc = layers.rmsnorm(x, xp["norm"])
                ap = xp["attn"]
                ck = layers._split_heads(qdot(cross_ctx, ap["wk"], qcfg),
                                         cfg.n_kv, cfg.hd)
                cv = layers._split_heads(qdot(cross_ctx, ap["wv"], qcfg),
                                         cfg.n_kv, cfg.hd)
                att, _ = layers.attention(
                    ap, hc, None, qcfg, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
                    head_dim=cfg.hd, causal=False, cross_kv=(ck, cv),
                    rope_theta=0.0)
                x = x + att
            return (x, aux + a), nc

        if has_cross and slot_cache is not None:
            xs = (slot_params, slot_cache, cross_params)
        elif has_cross:
            xs = (slot_params, cross_params)
        elif slot_cache is not None:
            xs = (slot_params, slot_cache)
        else:
            xs = slot_params
        from .sharding import remat_active
        if remat_active():
            body = jax.checkpoint(body)
        # pure-inference steps unroll shallow layer stacks: XLA schedules
        # across layers and the scan machinery drops out of the decode
        # floor (~2-3% at smoke scale); training keeps the rolled scan
        # (compile-time O(1) in depth)
        unroll = cfg.n_units if (qcfg.inference and cfg.n_units <= 8) else 1
        (x, aux_total), nc = pscan(body, (x, aux_total), xs, unroll=unroll)
        new_caches.append(nc)
    return x, new_caches, aux_total


def forward_train(params, batch, cfg: ArchConfig, qcfg: QuantConfig):
    """batch: tokens (B,S), labels (B,S), optional frontend embeddings.
    Returns (loss, metrics)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = layers.embed(params["embed"], tokens)
    x = constrain(x, "batch", None, "embed")
    positions = jnp.arange(S)
    cross_ctx = None
    if cfg.family == "encdec":
        enc_out = _run_encoder(params, batch["frontend"], cfg, qcfg)
        # precompute cross k/v once per layer? keep simple: pass enc_out and
        # project per layer inside cross attention via wk/wv of that layer.
        cross_ctx = enc_out
    if cfg.family == "vlm":
        # visual prefix (stub embeddings) prepended
        prefix = batch["frontend"]
        if "frontend_proj" in params:
            from repro.quant import qdot as _qd
            prefix = _qd(prefix, params["frontend_proj"], qcfg)
        x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
        positions = jnp.arange(x.shape[1])

    x, _, aux = _decoder_stack(params, x, positions, cfg, qcfg,
                               cross_ctx=cross_ctx)

    x = layers.rmsnorm(x, params["final_norm"])
    if cfg.family == "vlm":
        x = x[:, -S:]
    logits = layers.unembed(params["embed"], x, qcfg)
    logits = constrain(logits, "batch", None, "vocab")
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    mask = batch.get("mask", jnp.ones_like(nll))
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = loss + 0.01 * aux
    return loss, {"loss": loss, "aux": aux,
                  "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0))}


def forward_decode(params, state, tokens, cfg: ArchConfig, qcfg: QuantConfig):
    """One decode step — or a full-sequence PREFILL: tokens (B, S) with
    S > 1 runs the whole block causally against the fresh KV region in
    ONE pass (cache written in one slice, positions from the cache idx),
    which is exactly the fused-prefill regime: every qdot sees M = B·S
    rows, where the fused kernel's compute-scale win applies.  The
    decode state handed back is bit-identical to stepping the same
    tokens one by one (tests/test_prefill.py).  state from
    init_decode_state."""
    B, S = tokens.shape
    with obs.scope(obs.EMBED, tokens):
        x = layers.embed(params["embed"], tokens)
    positions = None  # decode positions come from caches (idx)
    cross_ctx = state.get("enc_out")
    if cfg.family == "encdec":
        cross_ctx = state["enc_out"]
    with obs.scope(obs.LAYERS, x):
        x, new_caches, _ = _decoder_stack(
            params, x, positions, cfg, qcfg, caches=state["caches"],
            cross_ctx=cross_ctx)
    with obs.scope(obs.FINAL_NORM, x):
        x = layers.rmsnorm(x, params["final_norm"])
    logits = layers.unembed(params["embed"], x, qcfg)
    new_state = dict(state, caches=new_caches)
    return logits, new_state


def _stack_tree(tree, n: int):
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), tree)


def init_decode_state(cfg: ArchConfig, batch: int, s_max: int,
                      enc_out=None, per_slot: bool = False) -> Dict:
    """``per_slot=True`` gives each batch slot an independent cache
    position (continuous batching: slots prefill/decode at their own
    depths; see launch/serve.py --continuous)."""
    caches = []
    for kind in cfg.pattern:
        if kind in ("attn", "moe"):
            one = layers.make_cache(batch, s_max, cfg.n_kv, cfg.hd,
                                    per_slot=per_slot)
        elif kind == "rec":
            one = recurrent.rglru_state(batch, cfg.d_rnn)
        elif kind == "mlstm":
            one = recurrent.mlstm_state(batch, cfg.n_heads,
                                        cfg.d_model // cfg.n_heads)
        elif kind == "slstm":
            one = recurrent.slstm_state(batch, cfg.d_model)
        else:
            raise ValueError(kind)
        caches.append(_stack_tree(one, cfg.n_units))
    state = {"caches": caches}
    if enc_out is not None:
        state["enc_out"] = enc_out
    return state
