"""The whole run with the timed path broken underneath must come out not
correct.  Each case skips only the harness's look for a chip: it runs
run.run on the CPU at the configuration's smoke widths (depth, traffic
and the cell's smoke limits) with ``--fault <kind>``, the same switch a
chip run at the cell's own size takes, and reads the result line.  The
faults (faults.py): the decode step returns its state unchanged, half
of the slots left out, a token altered where it is produced, the 4-bit
control in the program's place, and in the Design #2 qdot a wrong
gather index, the compensation left out, and the program's rank-r
emulation in place of the gather.  One chip, one program: there is no
exchange between chips to leave out.  A sound run, with the same
harness, comes out correct.
"""
import json

import pytest

import faults
import run

CELLS = [w["name"] for w in
         json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(capsys, cell, kind):
    argv = ["--workload", cell, "--seed", str(2**31 + 99),
            "--seconds", "0.3", "--trace", "0"]
    if kind != "sound":
        argv += ["--fault", kind]
    assert run.run(argv, smoke=True) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", ["sound", *faults.KINDS])
def test_broken_step_is_not_correct(capsys, cell, kind):
    line = _run(capsys, cell, kind)
    assert line["correct"] is (kind == "sound"), line["checks"]
