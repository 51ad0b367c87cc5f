"""Megabytes the fused engine brought back from the device per sweep: the
delta of ``ServiceStats.engine_fetch_bytes`` (the ``nbytes`` of every host
array the engine's fetch produced) over the delta of ``sweeps``, over 1e6.
A program without the counter reports nothing."""


def read(run):
    if "engine_fetch_bytes" not in run.stats0:
        return None
    sweeps = run.delta("sweeps")
    return run.delta("engine_fetch_bytes") / 1e6 / sweeps if sweeps else None
