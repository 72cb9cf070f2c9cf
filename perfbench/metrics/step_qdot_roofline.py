"""step_qdot_roofline: the served decode step's qdot calls' share of an
int8 matmul's roofline, in %.

Layer: qdot lowering (quant/linear.qdot -> kernels/ops.fused_qdot),
measured where the work happens: inside the decode step's executions
(jit_serve_step in the trace's "XLA Modules" line) in the window.  Its
device time is the union of the intervals of the ops the program puts
under a qdot scope (repro.obs.scope_table: each instruction of the
compiled step and its innermost jax.named_scope, qdot.<projection>), so a
while loop and the ops of its body count once.  The least time is, per
step, every layer's merged projections at M = slots, each an int8 (M, K)
x (K, N) matmul with the qdot's interface (roofline.int8_matmul,
roofline.least_time), as qdot_roofline counts its isolated calls.  No
value where the program names no scopes.

``scope_ns`` is the reduction the scope metrics share (unembed_roofline).
"""
import roofline
import tracefile

STEP = "jit_serve_step"


def step_executions(ctx) -> list:
    """(start, end) of the decode step's executions in the window (by
    their midpoint: the device's clock may lead the host's by a little)."""
    mods = ctx.devices[0]["modules"] if ctx.devices else []
    return [(s, e) for name, s, e, _ in mods
            if name.startswith(STEP + "(")
            and ctx.t0 <= (s + e) / 2 <= ctx.t1]


def scope_ns(ctx, keep):
    """(device ns, executions): the union of the intervals of the ops
    inside the step's executions whose scope passes ``keep``; None where
    the program has no scope table for the step."""
    try:
        from repro import obs
        table = obs.scope_table(STEP)
    except (ImportError, LookupError):
        return None, 0
    ops = [o for o in ctx.ops
           if keep(table.get(o[0].partition(" = ")[0].lstrip("%"),
                             obs.UNSCOPED))]
    ex = step_executions(ctx)
    return sum(tracefile.busy_ns(ops, s, e) for s, e in ex), len(ex)


def projections(c: dict) -> list:
    """(K, N) of one layer's merged serving projections."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    up = 2 * f if c["hidden_act"] == "silu" else f      # gate|up merged
    return [(d, (h + 2 * kv) * hd), (h * hd, d), (d, up), (f, d)]


def read(ctx):
    ns, steps = scope_ns(ctx, lambda s: s.split(".")[0] == "qdot")
    if not ns:
        return None
    M = len(ctx.window.positions[0])
    c = ctx.cfg
    least = steps * c["num_hidden_layers"] * sum(
        roofline.least_time(*roofline.int8_matmul(M, K, N), ctx.pk)
        for K, N in projections(c))
    return 100.0 * least / (ns / 1e9)
