"""omega_t_b_roofline.nystrom: the Nyström pair's second stage, C =
Omega^T·B, against its own least time.

The least time is the larger of the stage's operations over peak FLOP/s
and its bytes over HBM bandwidth (``stage2_flops`` / ``stage2_bytes`` of
the driver's work, ``bench/pair.py``).  The device time per call is the
time of the operations whose short name starts with ``sketch_omega_t_b``
(the kernel's ``pallas_call`` name) in the window, over the calls
completed.  None where the trace shows no such operation.
"""
from bench import trace, work

KERNEL = "sketch_omega_t_b"


def read(r):
    w = r.outcome.work
    if r.trace is None or not r.outcome.calls or not w \
            or "stage2_flops" not in w:
        return None
    secs = sum(s for name, s in trace.op_seconds(r.trace, r.window,
                                                 r.devices)
               if name.startswith(KERNEL))
    if secs <= 0:
        return None
    least = work.least_seconds({"flops": w["stage2_flops"],
                                "bytes": w["stage2_bytes"]},
                               r.peak, r.chips)["seconds"]
    return 100.0 * least / (secs / r.outcome.calls)
