"""train_step / serve_step factories (the functions the launcher jits).

Includes the scale-out machinery:
  * microbatched gradient accumulation (lax.scan) — overlaps each
    microbatch's backward collectives with the next one's compute (XLA
    latency-hiding scheduler does the interleave; the scan structure is
    what makes it possible);
  * optional remat (checkpointing) of each layer-scan body;
  * int8 gradient compression with error feedback (optimizer.py);
  * loss/metric psum-free design: metrics come out sharded-averaged.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import transformer as T
from repro.models.transformer import ArchConfig
from repro.quant import QuantConfig
from . import optimizer as opt_mod
from .optimizer import OptConfig


def make_loss_fn(cfg: ArchConfig, qcfg: QuantConfig, remat: bool = False,
                 params_transform=None):
    """params_transform: optional pure fn applied to params inside the
    loss (e.g. calib.plan.make_plan_injector wrapping raw weights with
    per-layer design tables) — autodiff sees through it, so grads and
    the optimizer tree stay on the raw leaves."""
    from repro.models.sharding import remat_scope

    def loss_fn(params, batch):
        if params_transform is not None:
            params = params_transform(params)
        with remat_scope(remat):
            return T.forward_train(params, batch, cfg, qcfg)
    return loss_fn


def make_train_step(cfg: ArchConfig, qcfg: QuantConfig, ocfg: OptConfig,
                    microbatches: int = 1, remat: bool = True,
                    params_transform=None):
    loss_fn = make_loss_fn(cfg, qcfg, remat, params_transform)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                return x.reshape(microbatches, x.shape[0] // microbatches,
                                 *x.shape[1:])
            mbs = jax.tree.map(split, batch)

            def acc_fn(carry, mb):
                g_acc, l_acc = carry
                (loss, _), g = grad_fn(params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + loss), None

            zero_g = jax.tree.map(jnp.zeros_like, params)
            (grads, loss_sum), _ = jax.lax.scan(
                acc_fn, (zero_g, jnp.zeros(())), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss_sum / microbatches
            metrics = {"loss": loss}
        new_params, new_opt = opt_mod.apply(params, grads, opt_state, ocfg)
        metrics = dict(metrics, loss=loss,
                       grad_norm=jnp.sqrt(sum(
                           jnp.vdot(g, g) for g in jax.tree.leaves(grads)).real))
        return new_params, new_opt, metrics

    return train_step


def _greedy(logits):
    """The next token of each row: argmax of the last position's logits."""
    with obs.scope(obs.SAMPLE, logits):
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)


def make_serve_step(cfg: ArchConfig, qcfg: QuantConfig):
    """One batched decode step: (params, state, tokens) -> (logits, state).

    Greedy sampling included so the example driver can loop it.  Each
    trace (not call) of the step adds one to obs.TRACES_SERVE_STEP."""
    def serve_step(params, state, tokens):
        obs.count(obs.TRACES_SERVE_STEP)
        logits, state = T.forward_decode(params, state, tokens, cfg, qcfg)
        return _greedy(logits), logits, state
    return serve_step


def make_prefill_step(cfg: ArchConfig, qcfg: QuantConfig):
    """Full-sequence fused prefill: one M = B·S pass through the decode
    stack, replacing launch/serve.py's old token-by-token prompt loop.

    (params, state, tokens (B, P)) -> (next_tok (B, 1), logits (B, P, V),
    state), where ``state`` is the post-prefill decode state — causal
    attention over the fresh KV block, cache written in one slice, and
    the handoff bit-identical to stepping the prompt token by token
    (tests/test_prefill.py).  Every qdot in the pass sees M = B·P rows,
    the regime where the fused quantize->delta->dequant kernel's
    compute-scale win applies (BENCH_kernels.json `serve_prefill`).

    Dynamic activation quantization runs PER POSITION inside the pass
    (QuantConfig.act_per_pos): each sequence slice quantizes over the
    same (B, 1, K) block the token loop would, so uncalibrated serving
    is also bit-identical to the loop.  Static/calibrated trees ignore
    the flag (their scales are fixed per layer already).  Each trace adds
    one to obs.TRACES_PREFILL_STEP."""
    import dataclasses
    qcfg_prefill = dataclasses.replace(qcfg, act_per_pos=True)

    def prefill_step(params, state, tokens):
        obs.count(obs.TRACES_PREFILL_STEP)
        logits, state = T.forward_decode(params, state, tokens, cfg,
                                         qcfg_prefill)
        return _greedy(logits), logits, state
    return prefill_step


def make_prefill_logits(cfg: ArchConfig, qcfg: QuantConfig):
    """Cache-free full-sequence forward (the dry-run's prefill-shape
    lowering): (params, batch) -> logits tail."""
    def prefill_logits(params, batch):
        from repro.models import layers
        tokens = batch["tokens"]
        x = layers.embed(params["embed"], tokens)
        positions = jnp.arange(tokens.shape[1])
        cross = None
        if cfg.family == "encdec":
            cross = T._run_encoder(params, batch["frontend"], cfg, qcfg)
        if cfg.family == "vlm":
            prefix = batch["frontend"]
            if "frontend_proj" in params:
                from repro.quant import qdot
                prefix = qdot(prefix, params["frontend_proj"], qcfg)
            x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
            positions = jnp.arange(x.shape[1])
        x, _, _ = T._decoder_stack(params, x, positions, cfg, qcfg,
                                   cross_ctx=cross)
        x = layers.rmsnorm(x, params["final_norm"])
        return layers.unembed(params["embed"], x[:, -128:], qcfg)
    return prefill_logits
