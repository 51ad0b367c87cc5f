"""Host milliseconds per ``query_mc`` call stacking the packed inputs by
level and padding them (span ``bm.engine.stage`` in
``JaxSweepEngine.solve``)."""

import spans


def read(run):
    return spans.ms_per_call(run, "bm.engine.stage")
