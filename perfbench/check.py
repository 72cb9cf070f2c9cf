"""The comparison that decides ``correct``: served tokens against the plain
reference.

Once the window has closed and the program's state is freed, a sample of
the requests that produced tokens (drawn from the seed, the longest
always in it) is run through the reference, each as its prompt followed
by its served tokens.  At every served position the number read is the
gap by which the served token's reference logit lies below the
reference's best logit there; the run compares the widest such gap
(``gap_max``) with the cell's limit (``limits/<workload>.json``).  Valid
for greedy decoding, which every mix here uses.

The control (``control=True``, used by ``control.py`` and the tests,
never by a benchmark run) reads, at the same positions, the gap of the
token a 4-bit reference puts first.  With ``control_in_place`` (``run.py
--fault control``) those tokens are judged as gap_max in place of the
program's: the control put in the program's place, teacher-forced on the
served prefix.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np

import reference
import serving
import weights as weights_mod

HERE = Path(__file__).resolve().parent


def limits(workload: str, smoke: bool = False) -> dict:
    """{number: limit} of a cell, or of its smoke-width rehearsal."""
    data = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    return {k: v["limit"]
            for k, v in data["smoke" if smoke else "numbers"].items()}


def sample(requests: list, seed: int, max_tokens: int) -> list:
    """Requests to compare: the one with most served tokens, then others
    in an order drawn from the seed, while the served tokens stay within
    ``max_tokens``."""
    reqs = [r for r in requests if r.tokens]
    if not reqs:
        return []
    longest = max(reqs, key=lambda r: len(r.tokens))
    rest = [r for r in reqs if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        r = rest[int(i)]
        if n + len(r.tokens) > max_tokens:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def compare(cfg: dict, seed: int, requests: list, *, max_tokens: int = 512,
            control: bool = False, control_in_place: bool = False) -> dict:
    """Numbers read from the served tokens of ``requests``:
    gap_max (and control_gap_max with ``control``), tokens compared,
    tokens outside the vocabulary."""
    control = control or control_in_place
    reqs = sample(requests, seed, max_tokens)
    vocab = cfg["vocab_size"]
    bad = sum(1 for r in reqs for t in r.tokens if not 0 <= t < vocab)
    out = {"tokens_compared": sum(len(r.tokens) for r in reqs),
           "requests_compared": len(reqs), "tokens_outside_vocab": bad}
    if bad or not reqs:        # nothing sound to compare: fails any limit
        out["gap_max"] = 1e9
        return out
    w = weights_mod.make(cfg, seed)
    ref = reference.Model(cfg, w).calibrate(
        serving.calibration_prompts(cfg),
        cfg["serving"]["calibration"]["gen_tokens"])
    ctl = (reference.Model(cfg, w, bits=4).with_ranges(ref.ranges)
           if control else None)
    for leaf in jax.tree.leaves(w["units"]):
        leaf.delete()
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
            for r in reqs]
    served = [slice(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens))
              for r in reqs]
    logits = [lg[sl] for lg, sl in zip(ref.logits(seqs), served)]
    out["gap_max"] = max(reference.widest_gap(lg, np.asarray(r.tokens))
                          for lg, r in zip(logits, reqs))
    if ctl is not None:
        out["control_gap_max"] = max(
            reference.widest_gap(lg, np.asarray(lg4[sl].argmax(-1)))
            for lg, lg4, sl in zip(logits, ctl.logits(seqs), served))
        if control_in_place:
            out["gap_max"] = out["control_gap_max"]
    return out
