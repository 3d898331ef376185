"""Device: one minus the union of the device's op intervals over the traced
window.  Nothing to read without a device plane in the trace."""
LAYER = "device (TPU)"
UNIT = "share"
SOURCE = "device_trace"
MOVES = "p50_ms"


def read(run):
    if run.trace is None or run.trace.busy_s is None or run.trace.window_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
