"""What every run shares: its context, the measured window, the reading of
the trace and the result line.

A driver (``bench/drivers/<name>.py``) builds the cell's inputs, warms its
shapes, runs the window inside :meth:`Context.window`, checks the outputs
and returns an :class:`Outcome`.  :func:`result` turns that into the one
JSON line the run prints last.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from . import peaks as peaks_mod
from . import spec
from . import trace as trace_mod


class CompileCounter:
    """Counts JAX compilations (persistent-cache hits included) while
    ``armed``; a window that compiles has not warmed its shapes."""

    EVENTS = ("/jax/compilation_cache/cache_hits",
              "/jax/compilation_cache/cache_misses")
    DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.armed and name in self.EVENTS:
            self.count += 1

    def _duration(self, name, _secs, **_):
        if self.armed and name in self.DURATIONS:
            self.count += 1


@dataclasses.dataclass
class Context:
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    t0: float                       # perf_counter when the harness started
    compiles: CompileCounter
    log_dir: Optional[str] = None
    # set-up, step by step: seconds from the end of the previous step
    steps: Dict[str, float] = dataclasses.field(default_factory=dict)
    _t_step: Optional[float] = None

    def step(self, name: str) -> None:
        """Close the set-up step ``name`` now (the first starts at ``t0``)."""
        now = time.perf_counter()
        self.steps[name] = now - (self.t0 if self._t_step is None
                                  else self._t_step)
        self._t_step = now

    @property
    def config(self) -> Dict[str, Any]:
        return self.cell.config

    @property
    def traffic(self) -> Dict[str, Any]:
        return self.cell.traffic

    @contextlib.contextmanager
    def window(self):
        """The measured window: compiles are counted, and with ``--trace 1``
        the profiler records it.  Everything before it is set-up."""
        import jax
        if self.trace:
            self.log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.compiles.armed = True
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self.compiles.armed = False
            if self.trace:
                jax.profiler.stop_trace()

    def read_trace(self) -> Optional[trace_mod.Trace]:
        if not self.trace or self.log_dir is None:
            return None
        try:
            return trace_mod.load(trace_mod.find_xplane(self.log_dir))
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)
            self.log_dir = None


@dataclasses.dataclass
class Outcome:
    """What a driver hands back.  ``checks`` maps each number compared to
    its value; the limits come from the cell's limits file."""
    setup_s: float
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: Dict[str, float]
    memory_peak_bytes: int
    calls: int = 0
    window_compiles: int = 0
    work: Optional[Dict[str, float]] = None
    notes: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader is given."""
    outcome: Outcome
    trace: Optional[trace_mod.Trace]
    window: Optional[tuple]
    devices: List[int]
    peak: Dict[str, float]
    chips: int


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def judge(checks: Dict[str, float], cell: spec.Cell) -> Dict[str, Dict]:
    """Each number compared beside its limit."""
    out = {}
    for name, value in checks.items():
        out[name] = {"value": value, "limit": cell.limit(name)}
    return out


def passes(judged: Dict[str, Dict]) -> bool:
    return bool(judged) and all(
        isinstance(v["value"], float) and math.isfinite(v["value"])
        and v["value"] <= v["limit"] for v in judged.values())


def result(ctx: Context, out: Outcome) -> Dict[str, Any]:
    """The result line's object, keys in the contract's order with the
    numbers compared last."""
    import jax
    cell = ctx.cell
    dev = ctx.devices[0]
    judged = judge(out.checks, cell)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    device: Dict[str, Any] = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": out.memory_peak_bytes}
    metrics: Dict[str, Dict[str, Any]] = {}
    line: Dict[str, Any] = {"correct": passes(judged),
                            "attempted": out.attempted,
                            "failed": out.failed}
    if not ctx.trace:
        for m in cell.end_to_end:
            name = m["name"]
            value = out.setup_s if name == "setup_s" else \
                out.end_to_end.get(name)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        line["metrics"] = metrics
        line["device"] = device
    else:
        tr = ctx.read_trace()
        ids = [d.id for d in ctx.devices]
        win = tr.window() if tr is not None else None
        reading = Reading(out, tr, win, ids,
                          peaks_mod.peaks(dev.device_kind), cell.chips)
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        line["metrics"] = metrics
        if tr is not None:
            busy = trace_mod.mean_busy_ns(tr, win, ids) * 1e-9
            device["busy_s"] = busy
            device["window_s"] = (win[1] - win[0]) * 1e-9
        line["device"] = device
        if tr is not None:
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in
                               trace_mod.op_seconds(tr, win, ids)[:10]],
                "idle_gaps": [[n, s] for n, s in trace_mod.idle_by_host(
                    tr, win, ids[0])[:10]]}
    line["setup_steps"] = dict(ctx.steps)
    line["checks"] = judged
    return line

