"""Host microseconds of ``ScenarioPack.build`` (span ``bm.pack``: copy,
classify, ``_pack_proc_args``) per scenario the service accepted in the
window (``ServiceStats.scenarios``)."""

import spans


def read(run):
    t, rows = spans.ns(run, "bm.pack"), run.delta("scenarios")
    return t * 1e-3 / rows if t is not None and rows else None
