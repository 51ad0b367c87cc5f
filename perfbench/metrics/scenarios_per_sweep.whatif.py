"""Scenarios per fused sweep over the window: the coalescer's batch width,
from the deltas of ``ServiceStats.scenarios`` and ``.sweeps``."""


def read(run):
    sweeps = run.delta("sweeps")
    return run.delta("scenarios") / sweeps if sweeps else None
