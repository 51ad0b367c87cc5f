"""Host milliseconds per ``query_mc`` call in the Monte Carlo sampler
(span ``bm.mc.sample``: random bits, factors, one ``Scenario`` per draw)."""

import spans


def read(run):
    return spans.ms_per_call(run, "bm.mc.sample")
