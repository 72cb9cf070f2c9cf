"""Runs one benchmark cell once and prints its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up by name in BENCHMARK.json at the checkout's root:
its configuration file (configs/), its traffic mix (mixes/<traffic>.json)
and its metrics.  One process, on the chips it finds; it exits non-zero
with no result line where JAX finds no TPU, a device kind missing from
peaks.json, or fewer chips than the cell asks for.

A run: set-up (weights from the seed, the program's prepare_params with
calibration, compiles or compile-cache loads, the slot fill and one warm
step), then the closed loop for --seconds, then the checks against the
plain reference: the served tokens (check.py) and the program's Design #2
qdot at the decode shapes (qdot_check.py).  With --trace 1 the
window and isolated calls of each projection are traced, and the line
carries the per-layer metrics (metrics/<name>.py) in place of the
end-to-end ones.  The last line of standard output is one JSON object;
the numbers compared, with their limits, are the last lines of standard
error and the last key of that object.

``--fault <kind>`` plants one of faults.py's faults in the timed path, to
show ``correct`` coming out false at the cell's own size; the benchmark's
own runs never pass it.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import faults  # noqa: E402

# host spans the trace reduction attributes device time and idle gaps to
SPANS = ("serve_window", "decode_step", "host_tokens", "refill_prefill",
         "scatter", "isolated_calls")


class NoDevice(Exception):
    """The machine lacks what the cell needs: no result is printed."""


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=faults.KINDS, default=None,
                    help="plant a fault (faults.py); not a benchmark run")
    return ap.parse_args(argv)


def load_cell(workload: str, bench: Path = ROOT / "BENCHMARK.json") -> dict:
    """Everything BENCHMARK.json and the cell's files say about a cell."""
    spec = json.loads(bench.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((HERE / "mixes" / f"{w['traffic']}.json").read_text())

    def applies(m):
        return workload in m.get("workloads", [workload])
    return {"name": workload, "chips": w["chips"], "cfg": cfg, "mix": mix,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; NoDevice unless they are TPUs of
    a kind in the peaks table, at least ``chips`` of them."""
    import jax
    import roofline
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"JAX finds no TPU: its first device is "
                       f"{d.platform!r} ({d.device_kind})")
    try:
        roofline.peaks(d.device_kind)
    except KeyError as e:
        raise NoDevice(str(e)) from None
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class TraceContext:
    """What the per-layer readers read: the configuration, the device's
    peaks, the window, and the reduced trace of the traced run."""

    def __init__(self, cfg, pk, window, events, iso):
        import tracefile
        self.cfg, self.pk, self.window, self.iso = cfg, pk, window, iso
        self.spans = events["spans"]
        self.devices = events["devices"]
        (self.t0, self.t1), = tracefile.span_times(self.spans,
                                                   "serve_window")
        self.window_s = (self.t1 - self.t0) / 1e9
        busy = [tracefile.busy_ns(d["ops"], self.t0, self.t1)
                for d in self.devices]
        self.busy_s = statistics.mean(busy) / 1e9 if busy else 0.0

    @property
    def ops(self):
        return self.devices[0]["ops"] if self.devices else []


def traced_window(server, seconds: float, trace_dir: Path, calls):
    """The window under the profiler, then each isolated call once.
    Returns (window, events, iso timings)."""
    import jax
    import tracefile
    from serving import SPAN
    for _, _, _, _, fq, g8 in calls:               # warm, outside
        jax.block_until_ready((fq(), g8()))
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with SPAN("serve_window"):
            window = server.window(seconds)
        with SPAN("isolated_calls"):
            for _, _, _, _, fq, g8 in calls:
                jax.block_until_ready(fq())
                for _ in range(3):
                    jax.block_until_ready(g8())
    finally:
        jax.profiler.stop_trace()
    events = tracefile.extract(str(trace_dir), SPANS)
    shutil.rmtree(trace_dir, ignore_errors=True)
    mods = events["devices"][0]["modules"] if events["devices"] else []
    iso = [{"projection": name, "M": M, "K": K, "N": N,
            "calls_per_step": server.arch.n_layers,
            "qdot_ns": tracefile.module_ns(mods, f"jit_bench_qdot_{name}"),
            "int8_ns": tracefile.module_ns(mods, f"jit_bench_int8_{name}")}
           for name, M, K, N, _, _ in calls]
    return window, events, iso


def run(argv=None, *, smoke: bool = False) -> int:
    """One run of a cell.  ``smoke=True`` (the CPU rehearsal and the
    tests) skips the look for a chip and runs the configuration's smoke
    widths against the smoke limits."""
    args = parse(argv)
    cell = load_cell(args.workload)
    cfg = dict(cell["cfg"], **(cell["cfg"]["smoke"] if smoke else {}))
    try:
        import jax
        if not smoke:
            device = device_info(cell["chips"])
        else:
            d = jax.devices()[0]
            device = {"platform": d.platform, "kind": d.device_kind,
                      "count": len(jax.devices())}
    except NoDevice as e:
        print(f"[perfbench] no result: {e}", file=sys.stderr)
        return 2
    import check
    import roofline
    import serving
    import traffic
    from repro.kernels import platform
    platform.enable_compile_cache()

    server = serving.Server(cfg, cell["mix"], args.seed, args.fault)
    counts = server.setup()
    if args.fault in faults.STEP:
        faults.break_step(server, args.fault)
    calls = server.isolated_calls() if args.trace else []
    setup_s = time.perf_counter() - T0

    if args.trace:
        window, events, iso = traced_window(
            server, args.seconds, ROOT / ".perfbench_trace" / cell["name"],
            calls)
    else:
        window = server.window(args.seconds)
    outputs = server.run_qdot()
    device["memory_peak_bytes"] = memory_peak()
    server.free()

    import qdot_check
    limits = check.limits(cell["name"], smoke)
    nums = check.compare(cfg, args.seed, window.requests,
                         control_in_place=args.fault == "control")
    nums["qdot_gap"] = qdot_check.gap(server.sites, outputs)
    correct = all(nums[k] <= lim for k, lim in limits.items())

    attempted = sum(1 for r in window.requests
                    if any(window.start < t <= window.end for t in r.times))
    failed = sum(1 for r in window.requests
                 if any(not 0 <= t < cfg["vocab_size"] for t in r.tokens))
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    if args.trace:
        ctx = TraceContext(cfg, None, window, events, iso)
        metrics = {}
        if not smoke:           # device metrics come from a chip run only
            ctx.pk = roofline.peaks(device["kind"])
            for m in cell["per_layer"]:
                v = load_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        import tracefile
        result["breakdown"] = {
            "device_ops": tracefile.top_ops(ctx.ops, ctx.t0, ctx.t1),
            "idle_gaps": tracefile.idle_gaps(ctx.ops, ctx.spans, ctx.t0,
                                             ctx.t1)}
    else:
        gaps = window.gaps()
        span = window.end - window.start
        values = {"tokens_per_s": window.tokens_in() / span,
                  "itl_p95_ms": (traffic.percentile(gaps, 95) * 1e3
                                 if gaps else None),
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]
                   if values.get(m["name"]) is not None}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": nums[k], "limit": lim}
                        for k, lim in limits.items()}

    print(f"[perfbench] {cell['name']} seed {args.seed}: set-up "
          f"{setup_s:.3f} s ({counts['compile_requests']} compile requests, "
          f"{counts['cache_misses']} compiled); window "
          f"{window.end - window.start:.3f} s, {window.steps} steps, "
          f"{window.tokens_in()} tokens, {window.refills} refills, "
          f"{window.compiles} compile requests inside; compared "
          f"{nums['tokens_compared']} served tokens of "
          f"{nums['requests_compared']} requests and "
          f"{len(server.sites)} Design #2 projections"
          + (f"; FAULT PLANTED: {args.fault}" if args.fault else ""),
          file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    try:
        return run()
    except Exception:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
