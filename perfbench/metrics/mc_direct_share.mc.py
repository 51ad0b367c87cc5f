"""Share of the window's Monte Carlo draws that the service packed straight
from their factor arrays, in percent: the deltas of ``ServiceStats``
counters ``mc_draws_direct`` over ``mc_draws_direct`` plus
``mc_draws_materialized`` (scenarios built from draws).  A program without
these counters reports nothing."""


def read(run):
    if "mc_draws_direct" not in run.stats0:
        return None
    direct = run.delta("mc_draws_direct")
    total = direct + run.delta("mc_draws_materialized")
    return 100.0 * direct / total if total else None
