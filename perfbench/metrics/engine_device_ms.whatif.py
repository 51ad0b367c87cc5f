"""Device milliseconds of the fused engine per fused sweep: the summed
device time of the engine's executables in the traced window, over the
sweeps the service ran in it.  The executables are the jitted ``run`` of
``repro.sweep.jax_engine.JaxSweepEngine`` (XLA module ``jit_run``)."""

import tracing

PATTERN = r"^jit_run\b"


def read(run):
    if run.trace is None:
        return None
    ns, n = tracing.named_ns(run.trace.modules, PATTERN, *run.window_ns)
    sweeps = run.delta("sweeps")
    return ns * 1e-6 / sweeps if n and sweeps else None
