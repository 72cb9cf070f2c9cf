"""decode_attn_roofline: the decode-attention kernel's share of its
memory roofline over the traced window, in %.

Layer: decode attention (kernels/attention.decode_attention_step, a
Pallas kernel).  Device time: every execution of the kernel inside the
window: the custom calls of the trace's "XLA Ops" line whose instruction
is named after the kernel (KERNEL).  Least
time: for every decode step and layer, the bytes that step must move
(roofline.decode_attention_bytes: each slot's valid cache rows, K and V
in bfloat16, the new rows, the float32 query and output) over the
memory bandwidth.
"""
import roofline
import tracefile

KERNEL = "%decode_attention_step"


def read(ctx):
    ns = tracefile.op_ns(ctx.ops, KERNEL, ctx.t0, ctx.t1)
    if not ns:
        return None
    c = ctx.cfg
    nbytes = sum(roofline.decode_attention_bytes(
        pos, c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"]) for pos in ctx.window.positions)
    least = c["num_hidden_layers"] * nbytes / ctx.pk["hbm_bytes_per_s"]
    return 100.0 * least / (sum(ns) / 1e9)
