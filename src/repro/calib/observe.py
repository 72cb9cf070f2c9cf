"""Calibration runner: named observers over the model's qdot call sites.

The paper's closing argument is that an approximate multiplier's *error
pattern* — not just its mean error distance — determines application
quality.  Acting on that requires knowing what operand distribution each
layer actually feeds the multiplier.  This module records it:

  * ``Observer`` hooks ``quant.linear.qdot`` (via ``set_observer``) and
    records, per call site, the activation range (min/max/amax) plus
    256-bin histograms of the QUANTIZED activation and weight operands —
    exactly the index distribution the 256x256 error tables are defined
    over, so downstream scoring (calib.plan) is a direct expectation
    over the table.
  * Sites are named by the weight's params-tree path (recorded by
    ``prequantize_weights``) plus the scan indices of the enclosing
    stacked-layer/expert scans: ``units.0.attn.wq@3`` is layer 3 of
    unit-slot 0's query projection; MoE expert weights get
    ``...w_up@3.5`` (unit 3, expert 5).
  * Per-layer values inside jax.lax.scan are invisible to Python, so
    calibration runs EAGERLY with the unit scans unrolled: the model
    code routes its layer-stack scans through ``pscan``, which is
    jax.lax.scan verbatim unless an observer is active, in which case it
    is a Python loop that pushes the slice index onto the observer's
    name stack.  Calibration is offline; the slow unrolled pass never
    touches the serving graph.

The output is a ``CalibrationTable`` (JSON-serializable) consumed by
``calib.static`` (static activation scales) and ``calib.plan`` (the
per-layer design search).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant import linear as qlin
from repro.quant.quantize import QuantConfig


def site_key(path: str, idx) -> str:
    """Canonical site name: tree path + scan indices ('p@i.j'; bare path
    for weights outside any stacked scan)."""
    idx = tuple(idx)
    return path if not idx else path + "@" + ".".join(str(i) for i in idx)


def _new_site():
    return {"lo": np.inf, "hi": -np.inf, "amax": 0.0, "count": 0,
            "hist_x": np.zeros(256, np.int64), "hist_w": None,
            "w_shape": None}


class Observer:
    """Accumulates per-site activation/weight statistics across batches.

    Deterministic: stats are pure reductions of the calibration inputs
    in a fixed traversal order, so two passes over the same batches
    produce identical tables (asserted in tests).
    """

    def __init__(self, qcfg: QuantConfig):
        self.qcfg = qcfg
        self.sites: Dict[str, dict] = {}
        self._idx: list = []
        self.unroll = True
        self.skipped_traced = 0   # qdot calls seen inside residual scans

    # -- name-stack hooks (pscan) ------------------------------------
    def push(self, i: int) -> None:
        self._idx.append(i)

    def pop(self) -> None:
        self._idx.pop()

    # -- qdot hook ----------------------------------------------------
    def record(self, x, pre, cfg: QuantConfig) -> None:
        if isinstance(x, jax.core.Tracer):
            # still inside some jitted/scanned region (e.g. a time-step
            # scan) — per-layer unrolling doesn't reach here; counted so
            # coverage gaps are visible, not silent.
            self.skipped_traced += 1
            return
        key = site_key(pre.path, self._idx)
        s = self.sites.setdefault(key, _new_site())
        xnp = np.asarray(x, np.float64).reshape(-1)
        s["lo"] = min(s["lo"], float(xnp.min()))
        s["hi"] = max(s["hi"], float(xnp.max()))
        s["amax"] = max(s["amax"], float(np.abs(xnp).max()))
        s["count"] += int(xnp.size)
        s["hist_x"] += np.bincount(self._quantize(xnp, cfg), minlength=256)
        if s["hist_w"] is None:
            s["w_shape"] = tuple(int(d) for d in pre.shape[-2:])
            if pre.q is not None:
                qw = np.asarray(pre.q, np.int64).reshape(-1)
            else:
                qw = self._quantize(
                    np.asarray(pre.w, np.float64).reshape(-1), cfg,
                    shift=False)
            if cfg.signed:
                qw = qw + 128
            s["hist_w"] = np.bincount(qw, minlength=256)

    def _quantize(self, v: np.ndarray, cfg: QuantConfig,
                  shift: bool = True) -> np.ndarray:
        """Batch-dynamic quantization to the 256-entry index grid (what
        qdot does per call) — the histogram approximates the serving
        operand distribution."""
        if cfg.signed:
            scale = max(float(np.abs(v).max()) / 127.0, 1e-8)
            q = np.clip(np.round(v / scale), -128, 127).astype(np.int64)
            return q + 128 if shift else q
        lo, hi = float(v.min()), float(v.max())
        scale = max((hi - lo) / 255.0, 1e-8)
        zp = float(np.clip(np.round(-lo / scale), 0, 255))
        return np.clip(np.round(v / scale) + zp, 0, 255).astype(np.int64)

    def table(self) -> "CalibrationTable":
        if self.skipped_traced:
            import warnings
            warnings.warn(
                f"calibration observer skipped {self.skipped_traced} "
                f"qdot calls that ran under a still-traced scan (e.g. a "
                f"recurrent time-step scan): those sites are NOT in the "
                f"table and apply_calibration(strict=True) will reject "
                f"them — check calib.static.coverage() for the gap")
        return CalibrationTable(mode=self.qcfg.mode,
                                sites={k: dict(v) for k, v in
                                       sorted(self.sites.items())})


@dataclasses.dataclass
class CalibrationTable:
    """Per-site calibration statistics + the static quantizers they fix.

    mode: the QuantConfig.mode the table was observed under (histograms
    are indexed on that mode's 256-entry grid)."""
    mode: str
    sites: Dict[str, dict]

    def act_quant(self, key: str):
        """The static activation quantizer for a site: (scale, zp) for
        asym_u8 (min/max calibration), (scale, None) for sym_i8
        (absmax calibration)."""
        s = self.sites[key]
        if self.mode == "sym_i8":
            return max(s["amax"] / 127.0, 1e-8), None
        scale = max((s["hi"] - s["lo"]) / 255.0, 1e-8)
        zp = float(np.clip(np.round(-s["lo"] / scale), 0, 255))
        return scale, zp

    def merge(self, other: "CalibrationTable") -> "CalibrationTable":
        """Pool the statistics of two tables over the same model (the
        multi-batch reduction: min/max/amax extremes, count and
        histogram sums).  Lives next to _new_site() so the field list
        stays in one place; sites seen by only one table pass through."""
        if self.mode != other.mode:
            raise ValueError(f"cannot merge calibration tables of modes "
                             f"{self.mode!r} and {other.mode!r}")
        sites = {k: dict(v) for k, v in self.sites.items()}
        for k, s in other.sites.items():
            if k not in sites:
                sites[k] = dict(s)
                continue
            d = sites[k]
            d["lo"] = min(d["lo"], s["lo"])
            d["hi"] = max(d["hi"], s["hi"])
            d["amax"] = max(d["amax"], s["amax"])
            d["count"] = d["count"] + s["count"]
            d["hist_x"] = np.asarray(d["hist_x"]) + np.asarray(s["hist_x"])
            if d["hist_w"] is None:
                d["hist_w"], d["w_shape"] = s["hist_w"], s["w_shape"]
        return CalibrationTable(mode=self.mode, sites=sites)

    # -- serialization ------------------------------------------------
    def to_json(self) -> dict:
        sites = {}
        for k, s in self.sites.items():
            sites[k] = {
                "lo": s["lo"], "hi": s["hi"], "amax": s["amax"],
                "count": s["count"],
                "hist_x": np.asarray(s["hist_x"]).tolist(),
                "hist_w": (np.asarray(s["hist_w"]).tolist()
                           if s["hist_w"] is not None else None),
                "w_shape": (list(s["w_shape"]) if s["w_shape"] else None),
            }
        return {"version": 1, "kind": "CalibrationTable", "mode": self.mode,
                "sites": sites}

    @classmethod
    def from_json(cls, d: dict) -> "CalibrationTable":
        sites = {}
        for k, s in d["sites"].items():
            sites[k] = {
                "lo": float(s["lo"]), "hi": float(s["hi"]),
                "amax": float(s["amax"]), "count": int(s["count"]),
                "hist_x": np.asarray(s["hist_x"], np.int64),
                "hist_w": (np.asarray(s["hist_w"], np.int64)
                           if s["hist_w"] is not None else None),
                "w_shape": (tuple(s["w_shape"]) if s["w_shape"] else None),
            }
        return cls(mode=d["mode"], sites=sites)

    def save(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.to_json(), fh)

    @classmethod
    def load(cls, path: str) -> "CalibrationTable":
        with open(path) as fh:
            return cls.from_json(json.load(fh))


# ---------------------------------------------------------------------------
# Scan routing + runner
# ---------------------------------------------------------------------------

def pscan(body, init, xs, length=None, unroll=1):
    """jax.lax.scan, except under an active calibration observer it is a
    Python loop (eager, concrete per-layer values) that pushes the slice
    index onto the observer's site-name stack.  The model's stacked-
    layer/expert scans route through this so calibration sees every
    layer by name; the serving/training graphs are untouched (observer
    None -> verbatim lax.scan).  ``unroll`` forwards to lax.scan (the
    serving decode step unrolls shallow layer stacks — transformer
    _decoder_stack; training keeps the rolled scan for compile-time
    O(1) in depth)."""
    obs = qlin.get_observer()
    if obs is None or not getattr(obs, "unroll", False):
        return jax.lax.scan(body, init, xs, length=length, unroll=unroll)
    n = length if length is not None else jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        xi = None if xs is None else jax.tree.map(lambda a: a[i], xs)
        obs.push(i)
        try:
            carry, y = body(carry, xi)
        finally:
            obs.pop()
        ys.append(y)
    ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    return carry, ys


@contextlib.contextmanager
def observing(obs: Observer):
    """Install obs as THE process qdot observer for the duration."""
    qlin.set_observer(obs)
    try:
        yield obs
    finally:
        qlin.set_observer(None)


def calibrate(pparams, cfg, qcfg: QuantConfig,
              batches: Iterable[dict]) -> CalibrationTable:
    """Run training-shaped forward passes over ``batches`` (dicts as
    produced by configs.make_smoke_batch) with observers installed and
    return the table.  ``pparams`` must be prequantized
    (quant.prequantize_weights) so sites carry tree-path names."""
    from repro.models import transformer as T
    obs = Observer(qcfg)
    with observing(obs):
        for batch in batches:
            T.forward_train(pparams,
                            {k: jnp.asarray(v) for k, v in batch.items()},
                            cfg, qcfg)
    return obs.table()


def calibrate_decode(pparams, cfg, qcfg: QuantConfig, prompts,
                     gen_len: int = 0,
                     enc_frontend=None) -> CalibrationTable:
    """Decode-shaped calibration: feed ``prompts`` (B, P) int32 token by
    token (plus ``gen_len`` greedy continuations) through the eager,
    unrolled decode step — the distribution the serving plan targets."""
    from repro.models import transformer as T
    prompts = np.asarray(prompts)
    B, P = prompts.shape
    obs = Observer(qcfg)
    with observing(obs):
        enc_out = None
        if cfg.family == "encdec":
            enc_out = T._run_encoder(pparams, jnp.asarray(enc_frontend),
                                     cfg, qcfg)
        state = T.init_decode_state(cfg, B, P + max(gen_len, 1),
                                    enc_out=enc_out)
        logits = None
        for i in range(P):
            logits, state = T.forward_decode(
                pparams, state, jnp.asarray(prompts[:, i:i + 1]), cfg, qcfg)
        for _ in range(gen_len):
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            logits, state = T.forward_decode(pparams, state, tok, cfg, qcfg)
    return obs.table()
