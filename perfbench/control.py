"""Readings that set a cell's limits: the program's numbers on many seeds,
and the control's and the planted faults', in one process.

    python3 perfbench/control.py --workload <cell> --seconds <s> \
        --seeds 11 12 13 ... [--control-seeds 3] [--qdot-faults] [--smoke]

For each seed: set-up and a window of the cell's own length, exactly as
a benchmark run does them (serving.Server), then check.compare on the
served tokens and qdot_check.gap on the Design #2 qdot calls.  On the
first ``--control-seeds`` seeds it also reads the control: at the same
positions, the gap of the token a 4-bit reference puts first (check.py).
``--qdot-faults`` reads instead, without serving, qdot_gap of the sound
program and of each qdot fault of faults.py on every seed.  One JSON
line per reading on standard output, and the same lines in
chiprun_out/control_<cell>.jsonl (chip runs only).  ``--smoke`` runs the
configuration's smoke widths on the CPU: the readings the smoke limits
(limits/<cell>.json, "smoke") are set from.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run  # noqa: F401  (puts the program's src/ on sys.path)
import faults  # noqa: E402
import qdot_check  # noqa: E402


def readings(workload: str, seed: int, seconds: float, control: bool,
             smoke: bool = False) -> dict:
    import check
    import serving
    cell = run.load_cell(workload)
    cfg = dict(cell["cfg"], **(cell["cfg"]["smoke"] if smoke else {}))
    t0 = time.perf_counter()
    server = serving.Server(cfg, cell["mix"], seed)
    server.setup()
    t1 = time.perf_counter()
    window = server.window(seconds)
    outputs = server.run_qdot()
    server.free()
    t2 = time.perf_counter()
    nums = check.compare(cfg, seed, window.requests, control=control)
    nums["qdot_gap"] = qdot_check.gap(server.sites, outputs)
    return dict(nums, seed=seed, setup_s=t1 - t0, window_steps=window.steps,
                window_s=window.end - window.start,
                reference_s=time.perf_counter() - t2)


def qdot_readings(workload: str, seed: int, smoke: bool = False) -> list:
    """qdot_gap of the sound program and of each qdot fault, on one
    seed's operands at the cell's decode shapes (no serving)."""
    import jax
    import serving
    import weights
    cell = run.load_cell(workload)
    cfg = dict(cell["cfg"], **(cell["cfg"]["smoke"] if smoke else {}))
    qcfg = serving.Server(cfg, cell["mix"], seed).qcfg
    params = weights.make(cfg, seed)
    sites = qdot_check.operands(params, cfg, seed, cell["mix"]["streams"])
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    out = []
    for kind in (None, *faults.QDOT):
        with faults.table(kind):
            calls = qdot_check.program_calls(
                sites, qdot_check.program_config(qcfg, kind))
        outputs = {s.name: np.asarray(call()) for s, call in calls}
        out.append({"seed": seed, "fault": kind,
                    "qdot_gap": qdot_check.gap(sites, outputs)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--qdot-faults", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args()
    if not a.smoke:
        run.device_info(run.load_cell(a.workload)["chips"])
    from repro.kernels import platform
    platform.enable_compile_cache()
    out = run.ROOT / "chiprun_out" / f"control_{a.workload}.jsonl"
    for i, seed in enumerate(a.seeds):
        if a.qdot_faults:
            got = qdot_readings(a.workload, seed, a.smoke)
        else:
            got = [readings(a.workload, seed, a.seconds,
                            i < a.control_seeds, a.smoke)]
        for r in got:
            line = json.dumps(r)
            print(line, flush=True)
            if not a.smoke:
                out.parent.mkdir(exist_ok=True)
                with out.open("a") as fh:
                    fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
