"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of device-op intervals / window)."""

import tracing


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.window_ns
    return 100.0 * (1.0 - tracing.busy_ns(run.trace, lo, hi) / (hi - lo))
