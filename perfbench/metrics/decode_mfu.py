"""decode_mfu: the whole decode loop's share of the chip's int8 peak over
the traced window, in %.

Layer: model step (train.make_serve_step -> transformer.forward_decode).
Operations: 2 x the multiply-accumulates of every matmul the window's
work needs (roofline.decode_step_ops per step: projections, attention
over each slot's cache, unembedding; roofline.prefill_ops per refill),
over the window's host-clock length and the int8 peak, the highest peak
of the chip, so the share stays at or below 100 %.
"""
import roofline


def read(ctx):
    w = ctx.window
    ops = sum(roofline.decode_step_ops(ctx.cfg, pos) for pos in w.positions)
    ops += sum(roofline.prefill_ops(ctx.cfg, len(r.prompt))
               for r in w.requests if w.start < r.times[0] <= w.end)
    span = w.end - w.start
    return 100.0 * ops / span / ctx.pk["int8_ops_per_s"] if span > 0 else None
