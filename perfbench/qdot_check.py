"""Integer check of the program's Design #2 qdot at the cell's decode shapes.

The served cells multiply exactly (the Dadda tree), whose delta table is
all zeros, so the served-token comparison (check.py) cannot see the
stage-2 gather, the design's table or the mean-field compensation.  This
check drives those through the program's own qdot entry
(``quant.linear.qdot``, the configuration's backend, merged q|k|v and
gate|up wrappers) with Design #2, at every projection's (M, K, N) of the
window's decode step (M = slots), and compares each output with the
plain reference's ``_linear`` (gate-level ``design2.product``).

The operands are the benchmark's: layer 0's float weights of the run's
seed (``weights.make``), quantized by the reference's own rule, and
activations drawn from the seed on a stated grid (s_x = 2^-5,
z_x = 128), so both sides quantize them to the same integers.  The
program builds the rest itself: its delta table, its compensation
tables and cached compensation column sums (``attach_comp_cols``), and
the merged wrappers (``fuse_projections``).

The number compared is ``qdot_gap``: the widest |program - reference|
over every output of every projection, in units of one integer product
(s_x * s_w of the output's column).  A sound program differs by float32
rounding of its int32 accumulator alone.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import arch
import reference

DESIGN = "design2"
SX = 2.0 ** -5          # stated activation grid: x = (q - ZX) * SX
ZX = 128


@dataclasses.dataclass
class Site:
    """One projection of layer 0 as the decode step calls it."""
    name: str                 # the program's (merged) weight name
    members: tuple            # (group, member weight name) per column block
    q: list                   # per member: (K, N_i) int32 device array
    scale: list               # per member: float32 scalars
    zp: list
    x: jax.Array              # (M, K) float32 on the stated grid

    @property
    def shape(self):
        K = self.q[0].shape[0]
        return self.x.shape[0], K, sum(int(q.shape[1]) for q in self.q)


def operands(params: dict, cfg: dict, seed: int, M: int) -> list:
    """The check's operands from the run's float parameter tree (before
    the program consumes it): one Site per projection of layer 0, as the
    architecture module lists them (``projections``)."""
    grid = reference.Grid(8)
    unit = params["units"][0]
    rng = np.random.default_rng([seed, 8])
    sites = []
    for name, members, K, _ in arch.load(cfg).projections(cfg):
        qs, ss, zs = [], [], []
        for group, n in members:
            q, s, z = reference.quantize_weight(unit[group][n][0], grid)
            qs.append(q)
            ss.append(s)
            zs.append(z)
        qx = rng.integers(0, 256, (M, K))
        x = jnp.asarray((qx - ZX) * SX, jnp.float32)
        sites.append(Site(name, members, qs, ss, zs, x))
    return sites


def program_config(qcfg, fault: str | None = None):
    """The serving QuantConfig with Design #2; the faults the tests and
    ``run.py --fault`` plant change it (``nocomp``: compensation left
    out; ``residual``: the program's rank-r emulation in place of the
    exact gather)."""
    kw = {"design": DESIGN}
    if fault == "nocomp":
        kw["compensate"] = False
    elif fault == "residual":
        kw["backend"] = "residual_xla"
    return dataclasses.replace(qcfg, **kw)


def program_calls(sites: list, qcfg) -> list:
    """Compiled program calls, one per site: (site, call) where
    ``call()`` runs the program's qdot on the site's operands.  The
    wrappers are built the way ``serve.prepare_params`` finishes a
    calibrated tree: static activation scales installed, compensation
    columns cached, same-input projections merged."""
    from repro.calib import attach_comp_cols
    from repro.quant import qdot
    from repro.quant.linear import QuantizedWeight, fuse_projections
    tree = {}
    for s in sites:
        for (group, n), q, sw, zw in zip(s.members, s.q, s.scale, s.zp):
            tree.setdefault(group, {})[n] = QuantizedWeight(
                None, q, sw, zw,
                colsum=q.sum(0, keepdims=True).astype(jnp.float32),
                act_scale=jnp.float32(SX), act_zp=jnp.float32(ZX),
                mode=qcfg.mode, path=f"units.0.{group}.{n}")
    tree = attach_comp_cols({"units": [tree]}, qcfg)
    unit = fuse_projections(tree)["units"][0]
    calls = []
    for s in sites:
        w = unit[s.members[0][0]][s.name]

        def f(x, w):
            return qdot(x, w, qcfg)
        f.__name__ = f"bench_qdot_{s.name}"
        fq = jax.jit(f).lower(s.x, w).compile()
        calls.append((s, lambda fq=fq, x=s.x, w=w: fq(x, w)))
    return calls


def gap(sites: list, outputs: dict) -> float:
    """qdot_gap of the program's ``outputs`` ({site name: (M, N) array})
    against the reference, in integer products."""
    comp = tuple(jnp.asarray(t) for t in reference.error_tables(DESIGN))
    grid = reference.Grid(8)
    act = (jnp.float32(SX), jnp.float32(ZX))
    worst = 0.0
    for s in sites:
        got = np.asarray(outputs[s.name], np.float64)
        col = 0
        for q, sw, zw in zip(s.q, s.scale, s.zp):
            n = int(q.shape[1])
            want = reference._linear(s.x, (q, sw, zw), act, grid, comp,
                                     DESIGN)
            d = np.abs(got[:, col:col + n] - np.asarray(want, np.float64))
            unit = float(np.float32(SX) * np.asarray(sw, np.float32))
            worst = max(worst, float(d.max()) / unit)
            col += n
        if col != got.shape[-1]:
            return 1e9          # the program's output has other columns
    return worst
