"""repro.obs: the program's spans and counters, and the map from a
compiled step's instructions to the device scopes of forward_decode."""
import gc
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs, obs
from repro.kernels import platform
from repro.launch import serve
from repro.models import transformer as T
from repro.train import make_prefill_step, make_serve_step

ARCH = "qwen3-1.7b"
# every scope of the merged, calibrated serving tree's steps
STEP_SCOPES = {obs.EMBED, obs.LAYERS, obs.ATTENTION, obs.FINAL_NORM,
               obs.UNEMBED, obs.SAMPLE, "qdot.wqkv", "qdot.wo",
               "qdot.w_gateup", "qdot.w_down"}
# the instructions that run as device ops and must carry a scope
_RUNS = re.compile(r" (fusion|while|dot|custom-call|convolution)\(")


def work_ops(hlo: str, table: dict) -> dict:
    """instruction name -> opcode of the fusions, loops, dots and custom
    calls that run as device ops (those the scope table maps)."""
    out = {}
    for line in hlo.splitlines():
        m, k = obs._INSTR.match(line), _RUNS.search(line)
        if m and k and m.group(2) in table:
            out[m.group(2)] = k.group(1)
    return out


@pytest.fixture(scope="module")
def prepared():
    """The smoke serving tree (prequantized, calibrated, fused backend,
    merged projections) and the spans its preparation recorded."""
    args = serve.parse_args(["--smoke", "--calibrate", "1", "--requests",
                             "2", "--prompt-len", "1"])
    cfg, qcfg = configs.get_smoke(ARCH), serve.quant_config(args)
    obs.reset()
    params, _ = serve.prepare_params(
        T.init_params(jax.random.PRNGKey(0), cfg), cfg, qcfg, args)
    return cfg, qcfg, params, obs.spans()


@pytest.mark.parametrize("make,tokens", [(make_serve_step, 1),
                                         (make_prefill_step, 3)])
def test_step_ops_resolve_to_scopes(prepared, make, tokens):
    cfg, qcfg, params, _ = prepared
    state = T.init_decode_state(cfg, 2, 8, per_slot=True)
    hlo = jax.jit(make(cfg, qcfg)).lower(
        params, state, jnp.zeros((2, tokens), jnp.int32)).compile().as_text()
    table = obs.scopes_of_hlo(hlo)
    ops = work_ops(hlo, table)
    assert {"fusion", "dot"} <= set(ops.values())
    unscoped = [n for n in ops if table[n] == obs.UNSCOPED]
    assert not unscoped, unscoped[:5]
    assert STEP_SCOPES <= set(table.values())


def test_prepare_params_spans(prepared):
    spans = {s.name: s for s in prepared[3]}
    parent = {s.name: s.parent for s in prepared[3]}
    assert parent == {
        obs.PREPARE_PARAMS: None, obs.PREQUANTIZE: obs.PREPARE_PARAMS,
        obs.CALIBRATE: obs.PREPARE_PARAMS,
        obs.CALIBRATE_BATCH: obs.CALIBRATE,
        obs.APPLY_CALIBRATION: obs.CALIBRATE,
        obs.ATTACH_COMP_COLS: obs.PREPARE_PARAMS,
        obs.FUSE_PROJECTIONS: obs.PREPARE_PARAMS}
    for name, p in parent.items():
        if p is not None:
            assert spans[p].start_ns <= spans[name].start_ns
            assert spans[name].end_ns <= spans[p].end_ns
    assert spans[obs.CALIBRATE].seconds > 0


def test_serve_continuous_traces_the_step_once(capsys):
    obs.reset()
    serve.main(["--arch", ARCH, "--smoke", "--continuous", "3",
                "--requests", "2", "--prompt-len", "2", "--gen-len", "3"])
    assert obs.counters()[obs.TRACES_SERVE_STEP] == 1
    assert obs.counters()[obs.TRACES_PREFILL_STEP] == 2   # B = 2 and 1
    out = capsys.readouterr().out
    assert "[serve] prefill: 6 prompt tokens" in out
    assert "[serve] decode: 6 tokens" in out
    assert "traces.serve_step=1" in out
    names = [s.name for s in obs.spans()]
    assert names.count(obs.PREFILL) == 2                  # B = 2, 1 refill
    assert names.count(obs.DECODE) >= 2


def test_serve_counts_the_qdot_lowering(capsys, monkeypatch):
    """With the qdot steered to the TPU's lowering (the onehot_qdot kernel,
    in interpret mode here), every qdot call site a step trace holds
    counts once under qdot.lowering.onehot and none under the twin's,
    and once under the kernel's contraction at its shape: the lane
    gather, as every row count here is at most 12."""
    monkeypatch.setitem(platform._AUTO, "cpu",
                        {"qdot": platform._AUTO["tpu"]["qdot"]})
    obs.reset()
    serve.main(["--arch", ARCH, "--smoke", "--continuous", "3",
                "--calibrate", "1", "--requests", "2", "--prompt-len", "2",
                "--gen-len", "3"])
    c = obs.counters()
    traces = c[obs.TRACES_SERVE_STEP] + c[obs.TRACES_PREFILL_STEP]
    # q|k|v, o, gate|up, down: the merged projections, one call site
    # each in the scanned layer body that a step trace traces once
    assert c[obs.QDOT_LOWERING_ONEHOT] == 4 * traces
    assert obs.QDOT_LOWERING_XLA not in c
    assert c[obs.QDOT_CONTRACTION_GATHER] == 4 * traces
    assert obs.QDOT_CONTRACTION_ONEHOT not in c
    out = capsys.readouterr().out
    assert f"qdot.lowering.onehot={4 * traces}" in out
    assert f"qdot.contraction.gather={4 * traces}" in out


def test_span_list_is_bounded():
    obs.reset()
    for i in range(obs.MAX_SPANS + 10):
        with obs.span(f"s{i}"):
            pass
    spans = obs.spans()
    assert len(spans) == obs.MAX_SPANS
    assert spans[0].name == "s10" and spans[-1].parent is None
    obs.count("c", 2)
    obs.count("c")
    assert obs.counters() == {"c": 3}
    obs.reset()
    assert obs.spans() == [] and obs.counters() == {}


def test_nested_spans_name_their_parent():
    obs.reset()
    with obs.span("outer") as outer:
        with obs.span("inner"):
            pass
    inner, = [s for s in obs.spans() if s.name == "inner"]
    assert inner.parent == "outer" and outer.parent is None
    assert outer.seconds >= inner.seconds >= 0


def test_scopes_open_only_while_traced():
    """Eager calls (the calibration pass) open no scope: it would only
    slow their dispatch."""
    import contextlib
    assert isinstance(obs.scope(obs.EMBED, jnp.ones(2)),
                      contextlib.nullcontext)
    kinds = []

    def f(x):
        kinds.append(type(obs.scope(obs.EMBED, x)))
        return x
    jax.jit(f)(jnp.ones(2))
    assert kinds and kinds[0] is not contextlib.nullcontext


HLO = """\
HloModule jit_step

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %tanh.1 = f32[4]{0} tanh(%param_0), metadata={op_name="jit(step)/layers/closed_call/qdot.wo/tanh" source_file="x.py"}
}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %b = f32[] parameter(1), metadata={op_name="reduce_sum"}
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %copy.7 = f32[4]{0} copy(%p)
}

ENTRY %main (x.1: f32[4]) -> f32[] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %dot_general.2 = f32[4]{0} dot(%x.1, %x.1), metadata={op_name="jit(step)/embed/dot_general"}
  %tanh_fusion = f32[4]{0} fusion(%dot_general.2), kind=kLoop, calls=%fused_computation
  %copy.3 = f32[4]{0} copy(%tanh_fusion)
  %t.4 = f32[4]{0} transpose(%copy.3), metadata={op_name="jit(step)/transpose(jvp(unembed))/mul"}
  %copy.5 = f32[4]{0} copy(%x.1)
  %while.8 = f32[4]{0} while(%copy.5), condition=%cond, body=%body, metadata={op_name="jit(step)/qdot.w_down/while"}
  ROOT %reduce.6 = f32[] reduce(%t.4, %copy.5), to_apply=%region_0, metadata={op_name="jit(step)/reduce_sum"}
}
"""


def test_scopes_of_hlo():
    assert obs.scopes_of_hlo(HLO) == {    # not the fused computation's
        "x.1": obs.UNSCOPED,                # nor the reducer's: inside others
        "dot_general.2": obs.EMBED,
        "tanh_fusion": "qdot.wo",      # its fused computation's root
        "copy.3": "qdot.wo",           # its operand's
        "t.4": obs.UNEMBED,            # unwrapped from transpose(jvp(.))
        "copy.5": obs.UNSCOPED,        # a copy of a parameter
        "while.8": "qdot.w_down",
        "p": "qdot.w_down", "copy.7": "qdot.w_down",   # the loop's
        "reduce.6": obs.UNEMBED}       # no scope in its op_name: operand's
    assert obs.qdot_scope("units.0.attn.wqkv") == "qdot.wqkv"
    assert obs.qdot_scope("") == obs.QDOT


def _probe(scope):
    def obs_probe_step(x):
        with jax.named_scope(scope):
            return jnp.tanh(x @ x.T)
    return jax.jit(obs_probe_step).lower(jnp.ones((4, 8))).compile()


def test_scope_table_outlives_the_executable(monkeypatch):
    monkeypatch.setattr(obs, "STEP_FUNCTIONS", ("obs_probe_step",))
    obs.reset()
    first = _probe(obs.UNEMBED)
    want = obs.scopes_of_hlo(first.as_text())
    assert obs.UNEMBED in want.values()
    assert obs.scope_table("jit_obs_probe_step") == want
    other = _probe(obs.SAMPLE)
    with pytest.raises(LookupError):          # two live, tables differ
        obs.scope_table("jit_obs_probe_step")
    del other
    gc.collect()
    assert obs.scope_table("jit_obs_probe_step") == want   # live first
    del first
    gc.collect()
    with pytest.raises(LookupError):          # two kept, tables differ
        obs.scope_table("jit_obs_probe_step")
    obs.reset()
    again = _probe(obs.UNEMBED)
    del again
    gc.collect()
    assert obs.scope_table("jit_obs_probe_step") == want   # kept, freed
    with pytest.raises(LookupError):
        obs.scope_table("jit_no_such_step")
