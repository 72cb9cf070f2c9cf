"""The architecture modules (arch/) and their one lookup.

* The qwen3 module gives, bit for bit, what the harness gave before the
  modules existed: weights, qdot-check operands, calibration ranges and
  reference logits at the configuration's smoke widths, and the program
  config, served tree and projection counts at its full widths
  (tests/data/qwen3_golden.json, tolerance 0).
* A module the harness has never seen, loaded from another directory,
  adds an untied ``lm_head`` leaf and takes its reference logits from it,
  with no harness file changed for it.
* A configuration whose ``model_type`` has no module is refused by
  ``run.load_cell`` before any device work, naming the file.
* Every configuration's ``prepared_spec`` matches a real
  ``prepare_params`` at its smoke widths.
"""
import hashlib
import json
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

import arch
import compile_v5e
import qdot_check
import roofline
import run
import serving
import weights

DATA = Path(__file__).resolve().parent / "data"
CONFIGS = sorted((run.HERE / "configs").glob("*.json"))
QWEN3 = run.HERE / "configs" / "qwen3-1.7b-L2-dadda.json"
MIX = json.loads((run.HERE / "mixes" / "reasoning.json").read_text())


def digest(a) -> str:
    a = np.asarray(a)
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def smoke(path: Path, **over) -> dict:
    cfg = json.loads(path.read_text())
    return dict(cfg, **cfg["smoke"], **over)


def spec_of(tree) -> dict:
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return {"treedef": str(treedef),
            "leaves": [[jax.tree_util.keystr(p), list(a.shape), str(a.dtype)]
                       for p, a in leaves]}


GOLDEN = json.loads((DATA / "qwen3_golden.json").read_text())


@pytest.mark.parametrize("seed", sorted(GOLDEN["seeds"]))
def test_qwen3_matches_the_readings_taken_before_the_move(seed):
    want = GOLDEN["seeds"][seed]
    cfg = smoke(QWEN3)
    w = weights.make(cfg, int(seed))
    assert {jax.tree_util.keystr(p): digest(a) for p, a in
            jax.tree_util.tree_flatten_with_path(w)[0]} == want["weights"]
    sites = [[s.name, [list(m) for m in s.members], digest(s.x),
              [digest(q) for q in s.q], [float(v) for v in s.scale],
              [float(v) for v in s.zp]]
             for s in qdot_check.operands(w, cfg, int(seed), 4)]
    assert sites == want["sites"]
    Model = arch.load(cfg).Model
    ref = Model(cfg, w).calibrate(
        serving.calibration_prompts(cfg),
        cfg["serving"]["calibration"]["gen_tokens"])
    assert json.loads(json.dumps(ref.ranges)) == want["ranges"]
    seqs = [np.asarray(s, np.int32) for s in GOLDEN["sequences"]]
    assert [digest(lg) for lg in ref.logits(seqs)] == want["logits"]
    ctl = Model(cfg, w, bits=4).with_ranges(ref.ranges)
    assert [digest(lg) for lg in ctl.logits(seqs)] == want["control_logits"]


def test_qwen3_full_widths_match_the_readings_taken_before_the_move():
    want = GOLDEN["full_widths"]
    cfg = json.loads(QWEN3.read_text())
    mod = arch.load(cfg)
    assert repr(mod.program_config(cfg)) == want["program_config"]
    qcfg = serving.Server(cfg, MIX, 1).qcfg
    assert spec_of(mod.prepared_spec(cfg, qcfg)) == want["prepared_spec"]
    assert roofline.projection_macs(cfg) == want["projection_macs"]
    assert [[K, N] for _, _, K, N in mod.projections(cfg)] == \
        want["projections"]


# qwen3 with an untied head: one more leaf, its own initial-value rule,
# and the reference's logits taken from it
UNTIED = '''
    """qwen3 with an untied lm_head (a test module)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference
    from arch import qwen3
    from arch.qwen3 import (GROUPS, head_bytes, layer_macs, prepared_spec,
                            program_config, projections)


    def _tied(cfg):
        return dict(cfg, tie_word_embeddings=True)


    def shapes(cfg):
        tree = qwen3.shapes(_tied(cfg))
        tree["lm_head"] = (cfg["hidden_size"], cfg["vocab_size"])
        return tree


    def leaf(key, names, shape):
        if names[-1] == "lm_head":
            return (jax.random.normal(key, shape, jnp.float32)
                    / np.sqrt(shape[0]))
        return qwen3.leaf(key, names, shape)


    class Model(qwen3.Model):
        def __init__(self, cfg, weights, bits=8):
            super().__init__(_tied(cfg), weights, bits)
            self.lm_head = weights["lm_head"]

        def _logits(self, x):
            x = qwen3.rmsnorm(x, self.final_norm, self.eps)
            return jnp.matmul(x, self.lm_head, precision=reference.HIGHEST)
'''


@pytest.fixture
def untied(tmp_path, monkeypatch):
    (tmp_path / "qwen3_untied.py").write_text(textwrap.dedent(UNTIED))
    monkeypatch.setattr(arch, "__path__", [*arch.__path__, str(tmp_path)])
    monkeypatch.delitem(sys.modules, "arch.qwen3_untied", raising=False)
    yield smoke(QWEN3, model_type="qwen3_untied", tie_word_embeddings=False)
    sys.modules.pop("arch.qwen3_untied", None)
    if hasattr(arch, "qwen3_untied"):
        delattr(arch, "qwen3_untied")


def bench_file(tmp_path, cfg: dict) -> Path:
    """A BENCHMARK.json with one cell of ``cfg`` on the reasoning mix."""
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    spec = {"configs": [{"name": "c", "file": str(tmp_path / "cfg.json")}],
            "workloads": [{"name": "c.reasoning", "config": "c",
                           "traffic": "reasoning", "chips": 1}],
            "end_to_end": [], "per_layer": []}
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def test_a_module_from_another_directory_adds_an_untied_head(untied,
                                                              tmp_path):
    cfg = untied
    assert run.load_cell("c.reasoning", bench_file(tmp_path, cfg))["cfg"] \
        == cfg
    w = weights.make(cfg, 3)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    assert w["lm_head"].shape == (d, V)
    assert w["embed"].shape == (V, d)
    mod = arch.load(cfg)
    ref = mod.Model(cfg, w).calibrate(serving.calibration_prompts(cfg))
    seqs = [np.arange(5, dtype=np.int32), np.arange(7, 16, dtype=np.int32)]
    got = ref.logits(seqs)
    assert [g.shape for g in got] == [(5, V), (9, V)]
    # with the head set to the embedding's transpose, the untied reference
    # is the tied one, bit for bit: only the head moved
    tied = {k: v for k, v in w.items() if k != "lm_head"}
    q3cfg = dict(cfg, model_type="qwen3", tie_word_embeddings=True)
    want = arch.load(q3cfg).Model(q3cfg, tied).with_ranges(
        ref.ranges).logits(seqs)
    same = mod.Model(cfg, dict(tied, lm_head=w["embed"].T)).with_ranges(
        ref.ranges).logits(seqs)
    for a, b, g in zip(want, same, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert not np.allclose(np.asarray(a), np.asarray(g))
    sites = qdot_check.operands(w, cfg, 3, 4)
    assert [(s.name, s.shape) for s in sites] == [
        (name, (4, K, N)) for name, _, K, N in mod.projections(cfg)]
    macs = roofline.projection_macs(cfg)
    assert macs == sum(K * N for _, _, K, N in mod.projections(cfg))
    assert roofline.decode_step_ops(cfg, [0]) == 2 * (
        cfg["num_hidden_layers"] * (macs + 2 * cfg["num_attention_heads"]
                                    * cfg["head_dim"]) + d * V)


def test_a_configuration_without_a_module_is_refused(tmp_path):
    cfg = smoke(QWEN3, model_type="no_such_arch")
    with pytest.raises(SystemExit) as e:
        run.load_cell("c.reasoning", bench_file(tmp_path, cfg))
    msg = str(e.value)
    assert "cfg.json" in msg and "no_such_arch.py" in msg, msg
    assert "qwen3.py" in msg, msg


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_prepared_spec_matches_prepare_params(path):
    compile_v5e.check_layout(json.loads(path.read_text()), MIX)


@pytest.mark.parametrize("entry", ["shapes", "program_config",
                                   "prepared_spec", "Model"])
@pytest.mark.parametrize("form", [{"hidden_act": "relu2"},
                                  {"qk_norm": False},
                                  {"tie_word_embeddings": False}])
def test_qwen3_refuses_a_file_that_states_other_forms(entry, form):
    cfg = dict(json.loads(QWEN3.read_text()), **form)
    fn = getattr(arch.load(cfg), entry)
    with pytest.raises(ValueError, match="qwen3 is SwiGLU"):
        fn(cfg) if entry in ("shapes", "program_config") else fn(cfg, None)
