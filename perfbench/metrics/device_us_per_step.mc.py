"""Device microseconds per lockstep step of the fused engine: the summed
device time of its executables (XLA module ``jit_run``) in the traced
window, over the delta of ``ServiceStats.engine_level_steps``.  A program
without the counter reports nothing."""

import tracing

PATTERN = r"^jit_run\b"


def read(run):
    if run.trace is None or "engine_level_steps" not in run.stats0:
        return None
    ns, n = tracing.named_ns(run.trace.modules, PATTERN, *run.window_ns)
    steps = run.delta("engine_level_steps")
    return ns * 1e-3 / steps if n and steps else None
