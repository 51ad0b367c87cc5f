"""Lockstep steps of the fused engine per sweep: the delta of
``ServiceStats.engine_level_steps`` (per sweep, the sum over topology levels
of that level's loop trip count) over the delta of ``sweeps``.  A program
without the counter reports nothing."""


def read(run):
    if "engine_level_steps" not in run.stats0:
        return None
    sweeps = run.delta("sweeps")
    return run.delta("engine_level_steps") / sweeps if sweeps else None
