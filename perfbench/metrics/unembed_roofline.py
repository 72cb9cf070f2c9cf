"""unembed_roofline: the output head's share of its roofline inside the
served decode step, in %.

Layer: output head (models/layers.unembed: the head's table, for qwen3
the tied float32 embedding transposed, against the final norm's
output).  Device time: the union of the intervals of the ops the
program puts under its unembed scope inside the decode step's
executions in the window (as step_qdot_roofline).  Least time per step:
the table's bytes (vocab x d_model x the architecture module's
``head_bytes``), the float32 activations in and logits out, over the
memory bandwidth, or 2·M·d·V operations over the bf16 peak, whichever
is longer; M = slots.  No value where the program names no scopes.
"""
import arch
from metrics.step_qdot_roofline import scope_ns


def read(ctx):
    ns, steps = scope_ns(ctx, lambda s: s == "unembed")
    if not ns:
        return None
    M = len(ctx.window.positions[0])
    d, V = ctx.cfg["hidden_size"], ctx.cfg["vocab_size"]
    nbytes = V * d * arch.load(ctx.cfg).head_bytes(ctx.cfg) + M * d * 4 \
        + M * V * 4
    least = max(nbytes / ctx.pk["hbm_bytes_per_s"],
                2 * M * d * V / ctx.pk["bf16_flops_per_s"])
    return 100.0 * steps * least / (ns / 1e9)
