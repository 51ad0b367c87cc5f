"""Device milliseconds of the fused engine per ``query_mc`` call: the summed
device time of the engine's executables (XLA module ``jit_run``) in the
traced window, over the calls that completed in it."""

import tracing

PATTERN = r"^jit_run\b"


def read(run):
    if run.trace is None:
        return None
    ns, n = tracing.named_ns(run.trace.modules, PATTERN, *run.window_ns)
    calls = len(run.driver.calls)
    return ns * 1e-6 / calls if n and calls else None
