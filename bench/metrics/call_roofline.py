"""call_roofline: the call's least time on the chip over its device time.

The least time is the larger of the call's operations over peak FLOP/s
and its bytes over HBM bandwidth (``bench/work.py``, from shapes; the
published bf16 peak, so that no implementation reads over 100%).  The
device time per call is the union of all device operations in the window,
averaged over the cell's chips, over the calls completed.
"""
from bench import trace, work


def read(r):
    if r.trace is None or not r.outcome.calls or r.outcome.work is None:
        return None
    busy = trace.mean_busy_ns(r.trace, r.window, r.devices) * 1e-9
    if busy <= 0:
        return None
    least = work.least_seconds(r.outcome.work, r.peak, r.chips)["seconds"]
    return 100.0 * least / (busy / r.outcome.calls)
