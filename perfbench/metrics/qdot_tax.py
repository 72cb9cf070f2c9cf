"""qdot_tax: device time of the approximate qdot over that of a plain
int8 x int8 -> int32 dot of the same (M, K, N), at the decode shapes.

Layer: qdot lowering.  Both calls run on their own in the traced run
(jit_bench_qdot_<projection>, the Design #2 calls of the integer check,
and jit_bench_int8_<projection>); each time is
the fastest execution in the trace's "XLA Modules" line, and projections
are weighted by their calls per decode step.  1 would mean the
approximate multiplier costs nothing over int8 arithmetic.
"""


def read(ctx):
    num = den = 0.0
    for c in ctx.iso:
        if not c["qdot_ns"] or not c["int8_ns"]:
            return None
        w = c["calls_per_step"]
        num += w * min(c["qdot_ns"])
        den += w * min(c["int8_ns"])
    return num / den if den > 0 else None
