"""The one traffic generator: reads a mix file's parameters and makes the
requests of a run from the seed.

A mix (``mixes/<name>.json``) states:

  loop           "closed": each of ``streams`` clients sends its next
                 request as soon as its last one has finished
  streams        concurrent clients (decode slots)
  prompt_tokens  a length distribution (below)
  output_tokens  a length distribution (below)
  queue          requests queued behind the first ``streams``
  decoding       "greedy"

A length distribution is {"values": [...], "weights": [...]} (discrete)
or {"log_uniform": [lo, hi]} (integers).

Every seed gets the same set of sizes: the first ``streams`` requests,
and separately the queue, take their lengths at the stratified
quantiles (i + 1/2) / n of each distribution.  The seed only shuffles
which request gets which length and draws the token ids, so two seeds
ask for the same amount of work, in another order.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # int32 token ids
    max_new: int                    # output tokens to produce
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new


def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Integer lengths at quantiles ``u`` of a length distribution."""
    if "values" in dist:
        vals = np.asarray(dist["values"], np.int64)
        w = np.asarray(dist["weights"], np.float64)
        cdf = np.cumsum(w / w.sum())
        return vals[np.minimum(np.searchsorted(cdf, u, side="right"),
                               len(vals) - 1)]
    if "log_uniform" in dist:
        lo, hi = dist["log_uniform"]
        return np.rint(lo * (hi / lo) ** u).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def largest(dist: dict) -> int:
    """The longest length a distribution can give."""
    for k in ("values", "log_uniform"):
        if k in dist:
            return int(max(dist[k]))
    raise ValueError(f"unknown length distribution {dist!r}")


def _stratified(dist: dict, n: int, rng) -> np.ndarray:
    return rng.permutation(quantile(dist, (np.arange(n) + 0.5) / n))


def closed_loop(mix: dict, vocab: int, seed: int):
    """(first, queue): the requests that fill the streams at set-up, and
    the ones sent, in order, as requests finish."""
    if mix["loop"] != "closed" or mix["decoding"] != "greedy":
        raise ValueError(f"mix not handled by this generator: {mix}")
    rng = np.random.default_rng(seed)
    out, rid = [], 0
    for n in (mix["streams"], mix["queue"]):
        plen = _stratified(mix["prompt_tokens"], n, rng)
        olen = _stratified(mix["output_tokens"], n, rng)
        batch = []
        for p, o in zip(plen, olen):
            ids = rng.integers(0, vocab, int(p), dtype=np.int64)
            batch.append(Request(rid, ids.astype(np.int32), int(o)))
            rid += 1
        out.append(batch)
    return out[0], out[1]


def cache_rows(mix: dict) -> int:
    """Cache rows a slot needs for the mix's longest request."""
    return largest(mix["prompt_tokens"]) + largest(mix["output_tokens"])


def percentile(values, q: float) -> float:
    """The q-th percentile (nearest rank) of ``values``."""
    v = sorted(values)
    return float(v[max(math.ceil(q / 100.0 * len(v)) - 1, 0)])
