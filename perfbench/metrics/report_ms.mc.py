"""Host milliseconds per ``query_mc`` call assembling reports: each sweep's
``Report`` (span ``bm.report``) and the Monte Carlo report over the chunks
(span ``bm.mc.report``)."""

import spans


def read(run):
    return spans.ms_per_call(run, "bm.report", "bm.mc.report")
