"""device_idle_share: the share of the traced window in which no
operation ran on the device, in %.

Layer: device.  1 - (union of the device-op intervals of the trace's
"XLA Ops" line inside the serve_window span) / (that span's length),
averaged over the chips used.
"""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.devices:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
