"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain event lists (nanoseconds on the profiler's one clock):

  spans    the benchmark's host spans (jax.profiler.TraceAnnotation):
           [name, start, end], for the names asked for
  devices  per TPU device plane: "ops", its "XLA Ops" line (one event
           per device operation: fusions, custom calls such as the
           Pallas kernels, copies), and "modules", its "XLA Modules"
           line (one event per execution of a compiled program, named
           after the jitted function, e.g. jit_bench_qdot_wqkv(...)),
           each event [name, start, end, description]

The rest works on those lists alone, so a small recorded trace kept
beside the tests (tests/data/) checks it without a chip.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def extract(trace_dir: str, span_names) -> dict:
    """Event lists of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    names = set(span_names)
    out = {"spans": [], "devices": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"plane": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.end_ns,
                                 _detail(e)] for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"].extend([e.name, e.start_ns, e.end_ns]
                                    for e in line.events if e.name in names)
    out["devices"].sort(key=lambda d: d["plane"])
    return out


_DETAIL_STATS = ("long_name", "tf_op", "hlo_category")


def _detail(event) -> str:
    """The op's longer description from its stats (the HLO text names
    the custom call's target and kernel), "" where there is none."""
    vals = [str(v) for k, v in event.stats if k in _DETAIL_STATS]
    return " ".join(vals)[:400]


def _clip(events, t0, t1):
    for ev in events:
        name, s, e = ev[0], max(ev[1], t0), min(ev[2], t1)
        if e > s:
            yield name, s, e


def busy_intervals(ops, t0, t1) -> list:
    """The union of the op intervals inside [t0, t1], merged, sorted."""
    iv = sorted((s, e) for _, s, e in _clip(ops, t0, t1))
    merged = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(ops, t0, t1) -> float:
    return float(sum(e - s for s, e in busy_intervals(ops, t0, t1)))


_KIND = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def short_name(hlo: str) -> str:
    """'%fusion.155 fusion' from an op's HLO text ('%fusion.155 = s32[..]
    fusion(...), kind=..., calls=...'): the instruction and its kind."""
    inst, _, rhs = hlo.partition(" = ")
    m = _KIND.search(rhs)
    return f"{inst} {m.group(1)}" if m else inst


def top_ops(ops, t0, t1, n: int = 10) -> list:
    """[[name, seconds]] of the device ops that took most time inside
    [t0, t1], summed by instruction and shortened (short_name)."""
    tot = {}
    for name, s, e in _clip(ops, t0, t1):
        tot[name] = tot.get(name, 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(k), v / 1e9] for k, v in best]


def label_at(spans, t) -> str:
    """The innermost benchmark span open at time ``t`` ("none" outside
    every span)."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


def idle_gaps(ops, spans, t0, t1, n: int = 10) -> list:
    """[[label, seconds]] of the longest gaps inside [t0, t1] in which no
    device op ran, each labelled with the host span open at its middle."""
    busy = busy_intervals(ops, t0, t1)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    return [[label_at(spans, (s + e) / 2), (e - s) / 1e9]
            for s, e in gaps[:n]]


def span_times(spans, name) -> list:
    """(start, end) of every span called ``name``, in order."""
    return sorted((s, e) for n, s, e in spans if n == name)


def module_ns(modules, prefix: str) -> list:
    """Durations of the executions of the compiled programs whose name
    starts with ``prefix`` (e.g. "jit_bench_qdot_wqkv")."""
    return [ev[2] - ev[1] for ev in modules if ev[0].startswith(prefix)]


def op_ns(ops, prefix: str, t0, t1) -> list:
    """Durations inside [t0, t1] of the device ops whose HLO instruction
    name starts with ``prefix`` (a Pallas kernel's custom call is named
    after the kernel: %decode_attention_step.4 = ... custom-call(...))."""
    return [min(ev[2], t1) - max(ev[1], t0) for ev in ops
            if min(ev[2], t1) > max(ev[1], t0)
            and ev[0].partition(" = ")[0].startswith(prefix)]
