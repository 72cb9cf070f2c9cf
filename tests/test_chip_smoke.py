"""chip_smoke.py on the CPU: its phase functions run at the smoke config
(Pallas in interpret mode), so the script cannot rot between chip runs,
and its main() refuses a machine without a TPU: non-zero exit, no result
line."""
import importlib.util
from pathlib import Path

import pytest

from repro import configs
from repro.kernels import platform

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_integer_core_and_attention_phases(smoke):
    smoke.phase_integer_core()
    err = smoke.phase_decode_attention(configs.get_smoke(smoke.ARCH), S=64)
    assert err <= smoke.ATTN_ATOL


@pytest.mark.parametrize("mode", ["asym_u8", "sym_i8"])
def test_serve_phase(smoke, mode):
    t = smoke.phase_serve(smoke.ARCH, mode, smoke=True, prompt_len=4,
                          gen_len=3)
    assert len(t["tokens"]) == smoke.REQUESTS
    assert all(len(r) == 3 for r in t["tokens"])
    assert any("fuse_projections" in n for n in t["notes"])


def test_main_refuses_cpu(smoke, monkeypatch, capsys):
    """No TPU: main returns non-zero before any phase and prints no
    result line (the compile-cache helper is stubbed: tests leave the
    process configuration alone)."""
    called = []
    monkeypatch.setattr(platform, "enable_compile_cache",
                        lambda: called.append(1) or "stub")
    assert smoke.main() != 0
    assert called == [1]
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when
    set, else the fixed <checkout>/.jax_cache."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert platform.compile_cache_dir() == str(ROOT / ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert platform.compile_cache_dir() == "/elsewhere/cache"
