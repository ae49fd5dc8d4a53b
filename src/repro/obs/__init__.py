"""repro.obs — runtime observability: spans, metrics, and the comm ledger.

Three instruments, one install pattern:

  * **metrics** (:mod:`.metrics`) — always-on process-global registry;
    counters/gauges/histograms with Prometheus text exposition.  The
    serving layer publishes into it unconditionally (the publish path is
    a dict hit + float add).
  * **tracer** (:mod:`.trace`) — program spans: annotations in any
    active ``jax.profiler`` trace, and a span timeline with
    Chrome/Perfetto export once ``install_tracer`` is called; with
    neither, ``span()`` is a shared no-op.
  * **ledger** (:mod:`.ledger`) — per-call-site measured collective bytes
    vs planner prediction vs the Theorem-2/3 floor; off by default
    (``install_ledger``).  ``report.honesty_report`` renders the audit;
    ``report.revalidate_autotune`` feeds drift back into the tuner cache.

``install_observability()`` turns everything on at once (the serve/bench
drivers use it behind ``--trace-out`` / ``--trace``).
"""
from .ledger import (CommLedger, LedgerSite, get_ledger, install_ledger,
                     uninstall_ledger)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_metrics, set_metrics)
from .report import (drift_flags, honesty_report, report_rows,
                     revalidate_autotune)
from .trace import (SpanRecord, Tracer, current_span_id, get_tracer,
                    install_tracer, span, uninstall_tracer)

__all__ = [
    "CommLedger", "LedgerSite", "get_ledger", "install_ledger",
    "uninstall_ledger",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_metrics",
    "set_metrics",
    "drift_flags", "honesty_report", "report_rows", "revalidate_autotune",
    "SpanRecord", "Tracer", "current_span_id", "get_tracer",
    "install_tracer", "span", "uninstall_tracer",
    "install_observability", "uninstall_observability",
]


def install_observability(max_spans: int = 100_000):
    """Install a fresh tracer + ledger (metrics are always on); returns
    ``(tracer, ledger, metrics)``."""
    return (install_tracer(Tracer(max_spans=max_spans)), install_ledger(),
            get_metrics())


def uninstall_observability():
    """Uninstall tracer and ledger; returns the previous ``(tracer,
    ledger)`` pair (the metrics registry stays installed)."""
    return uninstall_tracer(), uninstall_ledger()
