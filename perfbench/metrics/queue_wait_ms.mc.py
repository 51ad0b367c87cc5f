"""Milliseconds a request waits in the service, from submit to the start of
its own sweep, averaged over the window's requests (``ServiceStats``
counters ``queue_wait_s`` / ``queue_waits``)."""


def read(run):
    if "queue_waits" not in run.stats0:
        return None
    n = run.delta("queue_waits")
    return (run.stats1["queue_wait_s"] - run.stats0["queue_wait_s"]) \
        * 1e3 / n if n else None
