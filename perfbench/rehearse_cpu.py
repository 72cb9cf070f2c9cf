"""CPU rehearsal of the benchmark command: every cell of BENCHMARK.json at
its configuration's smoke widths (the file's "smoke" sizes, depth and
traffic as configured), untraced and traced, in this process with
JAX_PLATFORMS=cpu.  Checks the shape of the last line of standard
output, not its numbers: a CPU run says nothing about the chip.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_cpu.py [--seconds 2]
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys

import run

KEYS = ("correct", "attempted", "failed", "metrics", "device")


def rehearse(workload: str, seconds: float, trace: int) -> dict:
    """One in-process run on the CPU at smoke widths; returns its line."""
    cell = run.load_cell(workload)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.run(["--workload", workload, "--seed", str(2**31 + 7),
                      "--seconds", str(seconds), "--trace", str(trace)],
                     smoke=True)
    if rc != 0:
        raise SystemExit(f"{workload}: exit code {rc}")
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    missing = [k for k in KEYS if k not in line]
    if missing or list(line)[-1] != "checks":
        raise SystemExit(f"{workload}: bad result line {line}")
    if trace:
        if not {"busy_s", "window_s"} <= set(line["device"]) \
                or "breakdown" not in line:
            raise SystemExit(f"{workload}: traced line lacks device busy/"
                             f"window or breakdown: {line}")
    else:
        want = {m["name"] for m in cell["end_to_end"]}
        if set(line["metrics"]) != want:
            raise SystemExit(f"{workload}: metrics {sorted(line['metrics'])}"
                             f" != {sorted(want)}")
    return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--only", default=None)
    a = ap.parse_args()
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        if a.only and w["name"] != a.only:
            continue
        for trace in (0, 1):
            line = rehearse(w["name"], a.seconds, trace)
            print(f"[rehearse] {w['name']} trace={trace}: "
                  f"{json.dumps(line)}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
