"""qdot_roofline: the isolated qdot calls' share of an int8 matmul's
roofline, in %.

Layer: qdot lowering (quant/linear.qdot -> kernels/ops.fused_qdot).  In
the traced run each projection of layer 0 is called once on its own at
the decode shape (M = slots), with Design #2's table: the integer
check's calls (qdot_check.py), compiled programs named
jit_bench_qdot_<projection>.  Its device time is that program's
execution in the trace's "XLA Modules" line.  A speedup that only an
all-zero table allows does not show here.  The least time is what an int8
(M, K) x (K, N) matmul with the qdot's interface needs
(roofline.int8_matmul): 2MKN operations over the int8 peak, or int8
weights, float32 activations and outputs and four float32 column tables
over the memory bandwidth, whichever is longer.  Projections are
weighted by their calls per decode step.
"""
import roofline


def read(ctx):
    num = den = 0.0
    for c in ctx.iso:
        if not c["qdot_ns"]:
            return None
        ops, nbytes = roofline.int8_matmul(c["M"], c["K"], c["N"])
        w = c["calls_per_step"]
        num += w * roofline.least_time(ops, nbytes, ctx.pk)
        den += w * min(c["qdot_ns"]) / 1e9
    return 100.0 * num / den if den > 0 else None
