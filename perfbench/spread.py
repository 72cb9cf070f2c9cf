"""Runs a cell the way the benchmark's check does, and reports the spread
each bound is set from.

    python3 perfbench/spread.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 4 5 6 [--sets 2] [--traced-seeds 7 8 9]

Every run is its own process (``run.py``, as the check starts it; this
parent never touches JAX, so the child owns the chip).  Each set runs the
same seeds.  Every result line goes to chiprun_out/runs_<cell>.jsonl with
its seed, set and exit code; the summary gives, per end-to-end metric
and set, the median and the quartile spread (q3 - q1) / median, with
Python's statistics.quantiles(values, n=4), and the number compared with
its limit in every run.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True)
    line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else "{}"
    out = {"rc": p.returncode, "seed": seed, "trace": trace,
           "result": json.loads(line),
           "stderr_tail": p.stderr[-1500:]}
    return out


def spread(values: list) -> tuple:
    """(median, (q3 - q1) / median) of a set of runs."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q3 - q1) / med


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced-seeds", type=int, nargs="*", default=[])
    a = ap.parse_args()
    log = ROOT / "chiprun_out" / f"runs_{a.workload}.jsonl"
    log.parent.mkdir(exist_ok=True)
    sets = []
    with log.open("a") as fh:
        for k in range(a.sets):
            runs = []
            for seed in a.seeds:
                r = dict(one(a.workload, seed, a.seconds, 0), set=k)
                fh.write(json.dumps(r) + "\n")
                fh.flush()
                print(json.dumps({x: r[x] for x in ("set", "seed", "rc")}
                                 | {"line": r["result"]}), flush=True)
                runs.append(r)
            sets.append(runs)
        for seed in a.traced_seeds:
            r = one(a.workload, seed, a.seconds, 1)
            fh.write(json.dumps(r) + "\n")
            fh.flush()
            print(json.dumps({"traced": seed, "rc": r["rc"],
                              "line": r["result"]}), flush=True)
    for k, runs in enumerate(sets):
        ok = [r["result"] for r in runs if r["rc"] == 0 and r["result"]]
        names = sorted({m for r in ok for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in ok
                    if m in r["metrics"]]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(f"[spread] set {k} {m}: median {med!r} spread "
                      f"{sp!r} over {len(vals)} runs", flush=True)
        print(f"[spread] set {k} correct: "
              f"{[r.get('correct') for r in ok]} checks: "
              f"{[r.get('checks') for r in ok]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
