"""The readers of the program's own scopes and spans (step_qdot_roofline,
unembed_roofline, calibration_s) on hand-made events and HLO text, and on
one decode step recorded on the chip (tests/data/)."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import roofline
import run
from repro import obs

DATA = Path(__file__).resolve().parent / "data"
PK = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
      "hbm_bytes_per_s": 819e9}
CFG = {"hidden_size": 64, "intermediate_size": 192,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "num_hidden_layers": 2, "vocab_size": 512, "hidden_act": "silu"}

# a decode step: the qdot's K-blocked loop, whose body op runs inside the
# loop's own interval, the unembed, a layers fusion and a compiler copy
HLO = """\
HloModule jit_serve_step

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  ROOT %fusion.2 = (s32[], f32[4]) fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(serve_step)/layers/closed_call/qdot.w_gateup/add"}
}

ENTRY %main (x: (s32[], f32[4]), t: f32[512,64]) -> f32[4] {
  %x = (s32[], f32[4]) parameter(0)
  %t = f32[512,64]{1,0} parameter(1)
  %while.1 = (s32[], f32[4]) while(%x), condition=%cond, body=%body, metadata={op_name="jit(serve_step)/layers/closed_call/qdot.w_gateup/while"}
  %fusion.3 = f32[4,512]{1,0} fusion(%t), kind=kOutput, calls=%f3, metadata={op_name="jit(serve_step)/unembed/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%while.1), kind=kLoop, calls=%f4, metadata={op_name="jit(serve_step)/layers/closed_call/add"}
  ROOT %copy.5 = f32[512,64]{1,0} copy(%t)
}
"""


def _op(name, s, e):
    return [f"%{name} = f32[4]{{0}} op(...)", s, e, ""]


def ctx(ops, modules, positions=((4, 4, 4, 4),), t0=0, t1=10_000):
    dev = {"plane": "/device:TPU:0", "ops": ops, "modules": modules}
    return SimpleNamespace(cfg=CFG, pk=PK, t0=t0, t1=t1, devices=[dev],
                           ops=ops, window=SimpleNamespace(
                               positions=[list(p) for p in positions]))


STEP_OPS = [_op("while.1", 100, 600), _op("fusion.2", 150, 550),
            _op("fusion.3", 600, 900), _op("fusion.4", 900, 1000),
            _op("copy.5", 1000, 1050)]


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(obs, "scope_table",
                        lambda prefix: obs.scopes_of_hlo(HLO))


def _least_qdot(M):
    d, f = CFG["hidden_size"], CFG["intermediate_size"]
    qkv = (CFG["num_attention_heads"] + 2 * CFG["num_key_value_heads"]) \
        * CFG["head_dim"]
    shapes = [(d, qkv), (CFG["num_attention_heads"] * CFG["head_dim"], d),
              (d, 2 * f), (f, d)]
    return CFG["num_hidden_layers"] * sum(
        roofline.least_time(*roofline.int8_matmul(M, K, N), PK)
        for K, N in shapes)


def test_scope_readers_on_one_step(table):
    c = ctx(STEP_OPS, [["jit_serve_step(7)", 90, 1060, ""]])
    # qdot: the loop and its body op count once, 500 ns
    assert run.load_reader("step_qdot_roofline")(c) == pytest.approx(
        100 * _least_qdot(4) / 500e-9)
    V, d = CFG["vocab_size"], CFG["hidden_size"]
    least = max((V * d * 4 + 4 * d * 4 + 4 * V * 4) / PK["hbm_bytes_per_s"],
                2 * 4 * d * V / PK["bf16_flops_per_s"])
    assert run.load_reader("unembed_roofline")(c) == pytest.approx(
        100 * least / 300e-9)


def test_scope_readers_count_only_the_windows_decode_steps(table):
    ops = STEP_OPS + [_op(o[0][1:].split(" ")[0], o[1] + 2000, o[2] + 2000)
                      for o in STEP_OPS]
    ops += [_op("fusion.3", 5000, 5300)]          # a prefill's op
    mods = [["jit_serve_step(7)", 90, 1060, ""],
            ["jit_serve_step(7)", 2090, 3060, ""],
            ["jit_prefill_step(8)", 4990, 5400, ""],
            ["jit_serve_step(7)", 9990, 11060, ""]]  # mostly past t1
    one = run.load_reader("unembed_roofline")(
        ctx(STEP_OPS, mods[:1]))
    assert run.load_reader("unembed_roofline")(ctx(ops, mods)) == \
        pytest.approx(one)
    # an execution whose start the device clock puts a little before the
    # window still counts (its midpoint is inside)
    assert run.load_reader("unembed_roofline")(
        ctx(STEP_OPS, mods[:1], t0=120)) == pytest.approx(one)


def test_scope_readers_without_scopes(monkeypatch):
    """The parent program has no repro.obs scope table: no value."""
    def missing(prefix):
        raise LookupError(prefix)
    monkeypatch.setattr(obs, "scope_table", missing)
    c = ctx(STEP_OPS, [["jit_serve_step(7)", 90, 1060, ""]])
    assert run.load_reader("step_qdot_roofline")(c) is None
    assert run.load_reader("unembed_roofline")(c) is None


def test_calibration_s_reads_the_program_span():
    read = run.load_reader("calibration_s")
    obs.reset()
    assert read(None) is None
    with obs.span(obs.PREPARE_PARAMS):
        with obs.span(obs.CALIBRATE) as sp:
            pass
    assert read(None) == sp.seconds
    obs.reset()


def test_recorded_chip_step(monkeypatch):
    """One decode step recorded on the chip and the scopes of its ops."""
    path = DATA / "qwen3_step_scopes.json.gz"
    ev = json.load(gzip.open(path, "rt"))
    want = json.loads((DATA / "qwen3_step_scopes_expect.json").read_text())
    cfg = json.loads((run.ROOT / "perfbench" / "configs"
                      / "qwen3-1.7b-L2-dadda.json").read_text())
    pk = roofline.peaks("TPU v5 lite")
    dev = ev["devices"][0]
    (t0, t1), = [s[1:] for s in ev["spans"] if s[0] == "serve_window"]
    c = SimpleNamespace(cfg=cfg, pk=pk, t0=t0, t1=t1, devices=[dev],
                        ops=dev["ops"], window=SimpleNamespace(
                            positions=[[0] * ev["slots"]]))
    monkeypatch.setattr(obs, "scope_table", lambda prefix: ev["scopes"])
    got = {name: run.load_reader(name)(c)
           for name in ("step_qdot_roofline", "unembed_roofline")}
    assert got == pytest.approx(want["step"])
    # one step reads as the whole window did, and the served step's qdot
    # share as the isolated calls' (qdot_roofline) of the same run
    assert got == pytest.approx(
        {k: want["window"][k] for k in got}, rel=1e-3)
    assert 0.8 <= got["step_qdot_roofline"] / \
        want["window"]["qdot_roofline"] <= 1.25
    scopes = set(ev["scopes"].values())
    assert {"qdot.wqkv", "qdot.wo", "qdot.w_gateup", "qdot.w_down",
            obs.ATTENTION, obs.UNEMBED, obs.LAYERS} <= scopes
