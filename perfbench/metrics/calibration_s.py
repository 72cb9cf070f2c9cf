"""calibration_s: seconds the program's calibration took in set-up.

Layer: set-up: prepare_params (launch/serve.prepare_params: the eager
calibration pass over the configuration's calibration batches and the
static scales it installs).  The duration of the program's own
``calibrate`` span (repro.obs), read from the run's process after the
window; it ends once the scales are on the device.  No value where the
program records no such span.
"""


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    spans = [s.seconds for s in obs.spans() if s.name == obs.CALIBRATE]
    return sum(spans) if spans else None
