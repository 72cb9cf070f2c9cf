"""Plain reference of a served configuration, for the check that decides
``correct``: the numerical parts every architecture shares.  It imports
nothing of the program and takes nothing the program made: the harness
builds its weights from the seed (``weights.make``, the same call it
hands the program), and the architecture module's ``Model`` (a
``Decoder``, in ``arch/``) quantizes them, calibrates its own
static activation scales, multiplies through its own model of the
configuration's 8x8 multiplier (``PRODUCTS``: Design #2 gate by gate
from ``design2.py``, or the exact Dadda tree), and runs each sequence as
one causal pass in float32 at HIGHEST matmul precision.  The module's
docstring states its norms, rotary, MLP and head.

Semantics written down here, as the configuration states them:

* weights: per-tensor asymmetric uint8 per layer,
  q = clip(round(w / s) + z, 0, 255), s = max((max - min) / 255, 1e-8),
  z = clip(round(-min / s), 0, 255);
* activations: a static (s, z) per projection input and layer, from the
  minimum and maximum of that input over a calibration pass: the
  configuration's calibration prompts fed token by token, then two
  greedy tokens, with each call quantized over its own (B, 1, K) block
  by its own minimum and maximum;
* products: sum_k P(qx, qw) through the multiplier P, less the
  mean-field compensation sum_k mu_r[qx] + sum_k mu_c[qw] - K mu (the
  conditional means of P's error table), then the zero-point algebra
  - z_w rowsum(qx) - z_x colsum(qw) + K z_x z_w, times s_x s_w;
* K and V pass through the cache's dtype (bfloat16) before attention,
  which is grouped-query softmax attention in float32.

``bits=4`` gives the control: the same pass with weights and
activations on a 4-bit grid (levels 0..15, the same calibration ranges)
and exact integer products, the precision below int8.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import design2

HIGHEST = jax.lax.Precision.HIGHEST


# The 8x8 multipliers a configuration may state, as elementwise products:
# the paper's Design #2 gate by gate, and the Dadda tree (exact).
PRODUCTS = {"design2": design2.product, "dadda": lambda a, b: a * b}


def error_tables(design: str):
    """Mean-field compensation tables of a multiplier: row means mu_r[a],
    column means mu_c[b] and the overall mean of its error table."""
    a = np.arange(256, dtype=np.int64)
    err = PRODUCTS[design](a[:, None], a[None, :]) - a[:, None] * a[None, :]
    err = err.astype(np.float64)
    return (err.mean(1).astype(np.float32), err.mean(0).astype(np.float32),
            float(err.mean()))


def approx_matmul(qx, qw, design: str = "design2", k_block: int = 8):
    """sum_k P(qx[m, k], qw[k, n]) as int32 for the multiplier P of
    ``design``: exact products as an integer dot, Design #2 evaluated
    gate by gate in K-blocks (no table).  qx: (M, K), qw: (K, N), values
    in [0, 255]."""
    if design == "dadda":
        return jax.lax.dot(qx, qw, preferred_element_type=jnp.int32)
    product = PRODUCTS[design]
    M, K = qx.shape
    N = qw.shape[1]
    kb = math.gcd(K, k_block)
    xb = qx.reshape(M, K // kb, kb).transpose(1, 0, 2)
    wb = qw.reshape(K // kb, kb, N)

    def body(acc, blk):
        xk, wk = blk
        return acc + product(xk[:, :, None], wk[None]).sum(1), None

    out, _ = jax.lax.scan(body, jnp.zeros((M, N), jnp.int32), (xb, wb))
    return out


class Grid:
    """An asymmetric quantization grid of ``bits`` bits (8: uint8, 4: the
    control's 4-bit grid)."""

    def __init__(self, bits: int):
        self.bits = bits
        self.top = float(2 ** bits - 1)

    def params(self, lo, hi):
        """(scale, zero point) of a range, as float32 arrays."""
        lo = jnp.asarray(lo, jnp.float32)
        hi = jnp.asarray(hi, jnp.float32)
        s = jnp.maximum((hi - lo) / self.top, 1e-8)
        z = jnp.clip(jnp.round(-lo / s), 0, self.top)
        return s, z

    def quantize(self, x, s, z):
        return jnp.clip(jnp.round(x / s) + z, 0, self.top).astype(jnp.int32)


def quantize_weight(w, grid: Grid):
    """Per-tensor quantization of one (K, N) weight: (q, scale, zero point)."""
    s, z = grid.params(jnp.min(w), jnp.max(w))
    q = grid.quantize(w, s, z)
    return q, s, z


def _linear(x, wq, act, grid: Grid, comp, design: str):
    """Quantized projection of float x (M, K) by a prepared weight."""
    q, sw, zw = wq
    sx, zx = act
    qx = grid.quantize(x, sx, zx)
    K = x.shape[-1]
    rowsum = qx.sum(-1, keepdims=True)
    colsum = q.sum(0, keepdims=True)
    if grid.bits == 8:
        prod = approx_matmul(qx, q, design)
        mu_r, mu_c, mu = comp
        cterm = (jnp.take(mu_r, qx).sum(-1, keepdims=True)
                 + jnp.take(mu_c, q).sum(0, keepdims=True) - K * mu)
    else:
        prod = jax.lax.dot(qx, q, preferred_element_type=jnp.int32)
        cterm = 0.0
    zxi = zx.astype(jnp.int32)
    zwi = zw.astype(jnp.int32)
    yint = (prod - zwi * rowsum) - zxi * (colsum - K * zwi)
    return (yint.astype(jnp.float32) - cterm) * (sx * sw)


def _attention(q, k, v, mask):
    """GQA attention of q (T, H, hd) over k, v (S, Kv, hd) where mask
    (T, S) allows: each key-value head serves H / Kv query heads."""
    T, H, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(T, kv, H // kv, hd)
    lg = jnp.einsum("tngd,snd->ngts", qg, k,
                    precision=HIGHEST) / math.sqrt(hd)
    lg = jnp.where(mask[None, None], lg, -jnp.inf)
    out = jnp.einsum("ngts,snd->tngd", jax.nn.softmax(lg, -1), v,
                     precision=HIGHEST)
    return out.reshape(T, H, hd)


def _through_cache(a):
    """K or V as the cache holds it (bfloat16), back in float32."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


class Decoder:
    """The reference forward of one configuration at one grid, as far as
    every architecture shares it: the token-by-token calibration pass and
    the one causal pass per sequence.  An architecture module's ``Model``
    subclasses it and sets ``embed`` (the input table, (V, D)),
    ``layers`` (per layer, what its ``_layer`` takes), ``kv`` and ``hd``
    (key-value heads and their size), and defines

    * ``_layer(p, x, pos, attend, act_of)``: one layer over rows x (T, D)
      at positions pos (T,); ``attend(q, k, v)`` is the attention over
      the caller's keys (k and v pass through the cache's dtype there),
      and ``act_of(site, v)`` the (scale, zero point) a projection input
      is quantized with;
    * ``_logits(x)``: the final norm and the head.
    """

    def __init__(self, cfg: dict, bits: int = 8):
        self.grid = Grid(bits)
        self.design = cfg["serving"]["design"]
        mu_r, mu_c, mu = error_tables(self.design)
        self.comp = (jnp.asarray(mu_r), jnp.asarray(mu_c), jnp.float32(mu))
        self.ranges = None        # per layer: {site: (lo, hi)}

    def linear(self, wq, x, act):
        """Quantized projection of x by a weight from quantize_weight,
        with this model's grid and multiplier."""
        return _linear(x, wq, act, self.grid, self.comp, self.design)

    def calibrate(self, prompts: np.ndarray, gen_len: int = 2):
        """Static activation ranges from a token-by-token decode of
        ``prompts`` (B, P) plus ``gen_len`` greedy tokens, each call
        quantized by the minimum and maximum of its own (B, 1, K)
        block.  Returns self, with ``ranges`` set."""
        B, P = prompts.shape
        n = P + gen_len
        ranges = [dict() for _ in self.layers]
        caches = [(jnp.zeros((B, n, self.kv, self.hd)),) * 2
                  for _ in self.layers]
        tok = None
        for t in range(n):
            ids = jnp.asarray(prompts[:, t]) if t < P else tok
            x = jnp.take(self.embed, ids, axis=0)              # (B, D)
            for l, p in enumerate(self.layers):
                def act_of(site, v, r=ranges[l]):
                    lo, hi = jnp.min(v), jnp.max(v)
                    if site in r:
                        r[site] = (jnp.minimum(r[site][0], lo),
                                   jnp.maximum(r[site][1], hi))
                    else:
                        r[site] = (lo, hi)
                    return self.grid.params(lo, hi)

                def attend(q, k, v, l=l):
                    kc, vc = caches[l]
                    kc = kc.at[:, t].set(_through_cache(k))
                    vc = vc.at[:, t].set(_through_cache(v))
                    caches[l] = (kc, vc)
                    mask = (jnp.arange(n) <= t)[None]
                    return jnp.concatenate(
                        [_attention(q[b:b + 1], kc[b], vc[b], mask)
                         for b in range(B)], 0)

                x = self._layer(p, x, jnp.full((B,), t), attend, act_of)
            tok = jnp.argmax(self._logits(x), -1).astype(jnp.int32)
        self.ranges = [{s: (float(lo), float(hi)) for s, (lo, hi) in r.items()}
                       for r in ranges]
        return self

    def with_ranges(self, ranges):
        """Use calibration ranges found by another grid's pass (the
        4-bit control reuses the 8-bit pass's ranges)."""
        self.ranges = ranges
        return self

    def logits(self, seqs, pad_to: int = 128):
        """Logits of each token sequence in ``seqs`` (one causal pass
        per sequence, all rows through one set of projections).
        Returns a list of (T_i, V) arrays."""
        lens = [len(s) for s in seqs]
        total = -(-sum(lens) // pad_to) * pad_to
        ids = np.zeros(total, np.int32)
        pos = np.zeros(total, np.int32)
        seg = np.full(total, -1, np.int32)
        o = 0
        for i, s in enumerate(seqs):
            ids[o:o + len(s)] = s
            pos[o:o + len(s)] = np.arange(len(s))
            seg[o:o + len(s)] = i
            o += len(s)
        seg[o:] = -1 - np.arange(total - o)     # padding: own segment
        pos_, seg_ = jnp.asarray(pos), jnp.asarray(seg)
        mask = ((seg_[:, None] == seg_[None, :])
                & (pos_[None, :] <= pos_[:, None]))

        def attend(q, k, v):
            return _attention(q, _through_cache(k), _through_cache(v), mask)
        x = jnp.take(self.embed, jnp.asarray(ids), axis=0)
        for l, p in enumerate(self.layers):
            acts = {s: self.grid.params(lo, hi)
                    for s, (lo, hi) in self.ranges[l].items()}
            x = self._layer(p, x, pos_, attend,
                            lambda site, v, acts=acts: acts[site])
        lg = self._logits(x)
        out, o = [], 0
        for n_ in lens:
            out.append(lg[o:o + n_])
            o += n_
        return out


def widest_gap(ref_logits, tokens) -> float:
    """Largest amount by which a served token's reference logit lies
    below the reference's best logit at its position."""
    ref_logits = jnp.asarray(ref_logits)
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[:, None],
                              -1)[:, 0]
    return float((best - got).max())
