"""The program's own spans (``bm.*`` ``TraceAnnotation``s, listed in
PERF.md) in the traced window, for the per-layer readers in ``metrics/``.

The window's host events are read once per run (:func:`tracing.host_spans`)
and summed by name over every thread, clipped to the window.  A program
that writes no such span (an older parent) reads ``None``: the readers then
report nothing.
"""

import tracing


def totals(run) -> dict:
    """``{span name: ns inside the window}`` of the ``bm.*``
    spans of ``run``'s traced window, kept on the run after the first
    read."""
    if not hasattr(run, "bm_spans"):
        lo, hi = run.window_ns
        out: dict = {}
        for s, e, name, _thread in tracing.host_spans(run.trace, [(lo, hi)]):
            if name.startswith("bm."):
                out[name] = out.get(name, 0) + min(e, hi) - max(s, lo)
        run.bm_spans = out
    return run.bm_spans


def ns(run, *names):
    """Summed window time of the named spans, or ``None`` when the trace
    holds none of them."""
    if run.trace is None:
        return None
    got = [totals(run)[n] for n in names if n in totals(run)]
    return sum(got) if got else None


def ms_per_call(run, *names):
    """Milliseconds of the named spans per completed ``query_mc`` call."""
    t, calls = ns(run, *names), len(run.driver.calls)
    return t * 1e-6 / calls if t is not None and calls else None
