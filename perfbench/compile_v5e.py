"""Described-v5e compile rehearsal of every cell (no chip needed): the
programs a run drives, compiled at the cell's real widths for a TPU v5e
that the TPU compiler describes, not attached.

    JAX_PLATFORMS=cpu python3 perfbench/compile_v5e.py [--only <cell>]

Per cell: the decode step at B = slots over per-slot caches of the mix's
length, the B = 1 prefill at every prompt length of the mix, the
isolated qdot and int8 dot of each layer-0 projection at M = slots, and
the reference's gate-level product at its largest block of rows.  The
prepared serving tree is described by the architecture module's
``prepared_spec`` (``arch/``), whose layout is first checked against a
real ``prepare_params`` at the configuration's smoke widths on the CPU.
Prints each program's memory analysis; nothing runs, so this says
nothing about results or times.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402  (puts the program's src/ on sys.path)
import arch  # noqa: E402
import reference  # noqa: E402
import serving  # noqa: E402
import traffic  # noqa: E402


def check_layout(cfg: dict, mix: dict) -> None:
    """The architecture module's prepared_spec against a real
    prepare_params at the configuration's smoke widths."""
    import weights
    small = dict(cfg, **cfg["smoke"])
    srv = serving.Server(small, mix, 1)
    real, _ = srv.serve.prepare_params(weights.make(small, 1), srv.arch,
                                       srv.qcfg, srv.args)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), real)
    got = jax.tree.map(lambda a: (a.shape, a.dtype),
                       arch.load(small).prepared_spec(small, srv.qcfg))
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.leaves(want) != jax.tree.leaves(got)):
        raise SystemExit(f"prepared_spec differs from prepare_params:\n"
                         f"{want}\n{got}")


def compile_cell(workload: str) -> None:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import platform
    from repro.models import transformer as T
    from repro.quant import qdot
    from repro.train import make_prefill_step, make_serve_step

    cell = run.load_cell(workload)
    cfg, mix = cell["cfg"], cell["mix"]
    check_layout(cfg, mix)
    platform.backend = lambda: "tpu"        # the program's TPU choices
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    srv = serving.Server(cfg, mix, 1)
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    params = place(arch.load(cfg).prepared_spec(cfg, srv.qcfg))
    B, s_max = srv.slots, srv.s_max
    state = place(jax.eval_shape(lambda: T.init_decode_state(
        srv.arch, B, s_max, per_slot=True)))
    one_state = place(jax.eval_shape(lambda: T.init_decode_state(
        srv.arch, 1, s_max, per_slot=True)))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one)

    def report(name, fn, *args):
        t = time.perf_counter()
        c = jax.jit(fn).lower(*args).compile()
        m = c.memory_analysis()
        print(f"[compile_v5e] {workload} {name}: {time.perf_counter() - t:.1f}"
              f" s; arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.3f} GB", flush=True)
        return c

    report(f"decode step B={B} S_max={s_max}",
           make_serve_step(srv.arch, srv.qcfg), params, state, tok)
    lengths = sorted({len(r.prompt) for r in srv.first + srv.queue})
    for P in lengths:
        report(f"prefill B=1 P={P}", make_prefill_step(srv.arch, srv.qcfg),
               params, one_state,
               jax.ShapeDtypeStruct((1, P), jnp.int32, sharding=one))
    from repro.quant.linear import QuantizedWeight
    rows = 128 * -(-(B * (traffic.largest(mix["prompt_tokens"]) + 64))
                   // 128)
    for w in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedWeight)):
        if not isinstance(w, QuantizedWeight):
            continue
        name = w.path.rpartition(".")[2]
        w0 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape[1:], a.dtype, sharding=one), w)
        K, N = w0.shape
        x = jax.ShapeDtypeStruct((B, K), jnp.float32, sharding=one)
        report(f"isolated qdot {name} M={B} K={K} N={N}",
               lambda x, w: qdot(x, w, srv.qcfg), x, w0)
        report(f"isolated int8 dot {name}",
               lambda x, w: jax.lax.dot(
                   x, w, preferred_element_type=jnp.int32),
               jax.ShapeDtypeStruct((B, K), jnp.int8, sharding=one),
               jax.ShapeDtypeStruct((K, N), jnp.int8, sharding=one))
        report(f"reference product M={rows} K={K} N={N}",
               reference.approx_matmul,
               jax.ShapeDtypeStruct((rows, K), jnp.int32, sharding=one),
               jax.ShapeDtypeStruct((K, N), jnp.int32, sharding=one))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    a = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        if a.only is None or w["name"] == a.only:
            compile_cell(w["name"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
