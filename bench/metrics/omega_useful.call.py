"""omega_useful.call: the share of the Omega entries the kernels generated
that the products needed, in %.

Read from the program's ``omega_entries_needed_total`` and
``omega_entries_generated_total`` counters in the process-wide metrics
registry, summed over kernels.  None where the program publishes neither.
"""

GENERATED = "omega_entries_generated_total"
NEEDED = "omega_entries_needed_total"


def read(r):
    from repro.obs.metrics import get_metrics
    reg = get_metrics()
    if not {GENERATED, NEEDED} <= set(reg.names()):
        return None
    generated = sum(reg.counter(GENERATED).snapshot().values())
    needed = sum(reg.counter(NEEDED).snapshot().values())
    return 100.0 * needed / generated if generated else None
