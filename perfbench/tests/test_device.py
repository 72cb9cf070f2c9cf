"""Without a TPU of a known kind the run prints no result and fails."""
import json

import pytest

import run

CELL = json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"][0]


def test_cpu_gives_no_result(capsys):
    rc = run.run(["--workload", CELL["name"], "--seed", "1",
                  "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no result" in out.err


def test_unknown_device_kind(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v0 unknown"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(run.NoDevice):
        run.device_info(1)
