"""plan_host_ms.call: the host's median milliseconds in ``Plan.execute``
for the cell's sketch plan.

Read from the program's own ``plan_execute_seconds{task, variant}``
histogram in the process-wide metrics registry: the time ``execute``
takes to dispatch the call, not to finish it.  The series is the sketch
plan with the most observations (the cell runs one plan).  None where the
program publishes no such series.
"""


def read(r):
    from repro.obs.metrics import get_metrics
    reg = get_metrics()
    if "plan_execute_seconds" not in reg.names():
        return None
    hist = reg.histogram("plan_execute_seconds")
    series = [ls for ls in hist.labelsets() if ls.get("task") == "sketch"]
    if not series:
        return None
    labels = max(series, key=lambda ls: hist.count(**ls))
    return 1e3 * hist.percentile(50, **labels)
