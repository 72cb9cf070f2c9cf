"""The program's tracing: host spans, counters, and the map from a compiled
program's instructions to the program's device scopes.

Host side.  ``span(name)`` times a block on the host clock and, whenever
a ``jax.profiler`` trace is being taken, also writes it into that trace
(``jax.profiler.TraceAnnotation``), on the same clock as the device ops.
Closed spans are kept in a bounded list (the oldest are dropped), with
the name of the span that was open around them.  ``count(name)`` adds to
a counter.  ``spans()``, ``counters()`` and ``reset()`` read and clear
both.

Device side.  The model code opens ``jax.named_scope``s named by the
constants below at its layer boundaries (``scope``: only while a step is
being traced); they change no computation,
only the ``op_name`` metadata each instruction carries (and, as XLA
names instructions from it, some instruction numbers).
``scope_table(module_prefix)`` reads that metadata back from the
compiled program the backend holds (for the step functions, also once
it has been freed), as instruction name -> its innermost scope, so a
profiler trace's ops, which are named by instruction, can be put under
the program's layers.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
import threading
import time

import jax

# device scopes (jax.named_scope) of forward_decode and the step functions
EMBED = "embed"
LAYERS = "layers"              # the decoder stack around its blocks
ATTENTION = "attention"        # qk-norm, rope, cache append, attention
QDOT = "qdot"                  # qdot.<leaf of the weight's tree path>
FINAL_NORM = "final_norm"
UNEMBED = "unembed"
SAMPLE = "sample"              # greedy token choice of the step functions
SCOPES = (EMBED, LAYERS, ATTENTION, QDOT, FINAL_NORM, UNEMBED, SAMPLE)
UNSCOPED = "unscoped"          # no program scope (compiler copies, params)

# host spans of serve.prepare_params
PREPARE_PARAMS = "prepare_params"
PREQUANTIZE = "prequantize"
CALIBRATE = "calibrate"
CALIBRATE_BATCH = "calibrate_batch"
APPLY_CALIBRATION = "apply_calibration"
ATTACH_COMP_COLS = "attach_comp_cols"
FUSE_PROJECTIONS = "fuse_projections"

# host spans of launch/serve.py's runs
COMPILE = "compile"            # compiles and warm-up of the steps
PREFILL = "prefill"
DECODE = "decode"

# counters: traces (not calls) of the step functions
TRACES_SERVE_STEP = "traces.serve_step"
TRACES_PREFILL_STEP = "traces.prefill_step"
# counters: qdot call sites traced, by the lowering kernels.ops.fused_qdot
# chose (the one-hot contraction kernel or the blocked-XLA twin)
QDOT_LOWERING_ONEHOT = "qdot.lowering.onehot"
QDOT_LOWERING_XLA = "qdot.lowering.xla"

MAX_SPANS = 4096

# train/step.py's step functions: their scope tables are kept when they
# compile (or load from the persistent cache)
STEP_FUNCTIONS = ("serve_step", "prefill_step")
MAX_KEPT = 8
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def scope(name: str, x):
    """``jax.named_scope(name)`` where ``x`` is traced (a step being
    compiled); nothing on eager calls (the calibration pass), whose
    dispatch a scope only slows and whose ops no step trace holds."""
    if isinstance(x, jax.core.Tracer):
        return jax.named_scope(name)
    return contextlib.nullcontext()


def qdot_scope(path: str) -> str:
    """The scope of a qdot on the weight at params-tree ``path``
    ("units.0.attn.wqkv" -> "qdot.wqkv"; a bare weight -> "qdot")."""
    return f"{QDOT}.{path.rsplit('.', 1)[-1]}" if path else QDOT


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int | None         # None while the span is open
    parent: str | None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_counters: collections.Counter = collections.Counter()
_kept: dict = {}               # module name -> its distinct scope tables
_open = threading.local()


@contextlib.contextmanager
def span(name: str):
    """Time the block as span ``name``; yields its Span (``seconds`` is
    read once the block has ended)."""
    stack = _open.__dict__.setdefault("stack", [])
    sp = Span(name, time.perf_counter_ns(), None,
              stack[-1] if stack else None)
    stack.append(name)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield sp
    finally:
        sp.end_ns = time.perf_counter_ns()
        stack.pop()
        _spans.append(sp)


def count(name: str, n: int = 1) -> None:
    _counters[name] += n


def spans() -> list:
    """The closed spans, oldest first (at most MAX_SPANS)."""
    return list(_spans)


def counters() -> dict:
    return dict(_counters)


def reset() -> None:
    """Clear the spans, the counters and the kept scope tables."""
    _spans.clear()
    _counters.clear()
    _kept.clear()


_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_BODIES = re.compile(r"\b(?:body|condition)=%?([\w.\-]+)")
_INNER = re.compile(r"(?:\bfusion\(.*\bcalls|\bto_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scope_of(op_name: str) -> str:
    """The innermost program scope of an instruction's ``op_name``
    ("jit(serve_step)/layers/closed_call/qdot.wqkv/dot_general" ->
    "qdot.wqkv"); UNSCOPED where none of SCOPES is on the path.  Names a
    transformation wrapped ("jvp(qdot.wo)") are unwrapped."""
    for part in reversed(op_name.split("/")):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES or part.startswith(QDOT + "."):
            return part
    return UNSCOPED


def scopes_of_hlo(text: str) -> dict:
    """instruction name (no '%') -> innermost scope, for the instructions
    of an HLO module's text that run as device ops (those outside fused
    computations and reducers).  One whose metadata names no scope (the
    compiler's own rewrites and copies) takes the scope of the fused
    computation it calls (its root's, else the commonest there), else
    that of its first scoped operand, else, inside a loop, the loop's."""
    own, calls, bodies, uses, comps, root = {}, {}, {}, {}, {}, {}
    inner = set()
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c and " = " not in line:
                comp = c.group(1)
                comps[comp] = []
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else UNSCOPED
        calls[name] = _CALLS.findall(line)
        bodies[name] = _BODIES.findall(line)
        uses[name] = _OPERAND.findall(line[m.end():])
        inner.update(_INNER.findall(line))
        comps.setdefault(comp, []).append(name)
        if m.group(1):
            root[comp] = name

    def fused(comp):
        if own.get(root.get(comp), UNSCOPED) != UNSCOPED:
            return own[root[comp]]
        scoped = [own[n] for n in comps.get(comp, []) if own[n] != UNSCOPED]
        return (collections.Counter(scoped).most_common(1)[0][0]
                if scoped else UNSCOPED)

    table = {}
    for comp, names in comps.items():      # operands come first
        if comp in inner:
            continue
        for name in names:
            found = [own[name]] + [fused(c) for c in calls[name]] + [
                table.get(o, UNSCOPED) for o in uses[name]]
            table[name] = next((f for f in found if f != UNSCOPED),
                               UNSCOPED)
    # what is left in a loop's body or condition is the loop's
    loops = [(c, name) for name in table for c in bodies[name]]
    for _ in loops:                        # nested loops: one pass each
        for comp, loop in loops:
            for name in comps.get(comp, []):
                if table[name] == UNSCOPED:
                    table[name] = table[loop]
    return table


def _modules(module_prefix: str, first: bool = False):
    """The HLO modules of the live executables whose name starts with
    ``module_prefix``, newest first; only the newest with ``first``."""
    from jax.extend.backend import get_backend
    for exe in get_backend().live_executables():
        for m in exe.hlo_modules():
            if m.name.startswith(module_prefix):
                yield m
                if first:
                    return


def _keep(event: str, duration: float, fun_name: str = "", **_) -> None:
    """jax.monitoring listener: on each compile (or persistent-cache
    load) of a step function, keep the new executable's scope table,
    since a trace is often read after the program's executables are
    freed."""
    fn = fun_name.removeprefix("jit(").removesuffix(")")  # jit(serve_step)
    if event == _COMPILE_EVENT and fn in STEP_FUNCTIONS:
        for m in _modules(f"jit_{fn}", first=True):
            table = scopes_of_hlo(m.to_string())
            kept = _kept.setdefault(m.name, [])
            if table not in kept:
                kept[:] = kept[-(MAX_KEPT - 1):] + [table]


def scope_table(module_prefix: str) -> dict:
    """scopes_of_hlo of the live executable whose HLO module name starts
    with ``module_prefix`` (e.g. "jit_serve_step"); where none is live,
    of those compiled before (STEP_FUNCTIONS only, the last MAX_KEPT
    distinct tables of each).  Raises LookupError
    where none matches, or where several match and their tables differ."""
    tables = [scopes_of_hlo(m.to_string())
              for m in _modules(module_prefix)] or [
        t for name, ts in _kept.items() if name.startswith(module_prefix)
        for t in ts]
    if not tables:
        raise LookupError(f"no executable named {module_prefix}*")
    if any(t != tables[0] for t in tables[1:]):
        raise LookupError(f"{len(tables)} executables named "
                          f"{module_prefix}* map their instructions to "
                          f"different scopes")
    return tables[0]


jax.monitoring.register_event_duration_secs_listener(_keep)
