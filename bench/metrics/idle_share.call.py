"""idle_share.call: the share of the window in which no operation ran on
the device (averaged over the cell's chips), in the closed-loop cells."""
from bench import trace


def read(r):
    if r.trace is None:
        return None
    span = r.window[1] - r.window[0]
    busy = trace.mean_busy_ns(r.trace, r.window, r.devices)
    return 100.0 * (1.0 - busy / span)
