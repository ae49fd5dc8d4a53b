"""plan_host_ms.nystrom: the host's median milliseconds in ``Plan.execute``
for the cell's Nyström plan.

Read from the program's own ``plan_execute_seconds{task, variant}``
histogram in the process-wide metrics registry: the time ``execute``
takes to dispatch the pair's two launches, not to finish them.  The
series is the Nyström plan with the most observations (the cell runs one
plan).  None where the program publishes no such series.
"""


def read(r):
    from repro.obs.metrics import get_metrics
    reg = get_metrics()
    if "plan_execute_seconds" not in reg.names():
        return None
    hist = reg.histogram("plan_execute_seconds")
    series = [ls for ls in hist.labelsets() if ls.get("task") == "nystrom"]
    if not series:
        return None
    labels = max(series, key=lambda ls: hist.count(**ls))
    return 1e3 * hist.percentile(50, **labels)
