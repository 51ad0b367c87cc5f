"""Host milliseconds per ``query_mc`` call moving arrays between host and
device: ``device_args`` (span ``bm.engine.put``, the host side of the
transfer) and the result records (span ``bm.engine.fetch``)."""

import spans


def read(run):
    return spans.ms_per_call(run, "bm.engine.put", "bm.engine.fetch")
