"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A run with ``--trace 1`` records the measured window with the JAX
profiler.  :func:`load` reads the ``.xplane.pb`` it wrote into plain
intervals: the operations each device ran (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane), and the host spans that the benchmark itself
opened with ``jax.profiler.TraceAnnotation`` (names starting ``bench.``).
Everything else here works on those intervals alone, so the tests can give
it recorded or made-up ones.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # [start, end) in nanoseconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|psum|allreduce|allgather|reducescatter|alltoall", re.IGNORECASE)
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str

    @property
    def collective(self) -> bool:
        return bool(COLLECTIVE.search(self.name))


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    """Device operations per device id, and the benchmark's host spans."""
    ops: Dict[int, List[Op]]
    spans: List[Span]

    def window(self, name: str = HOST_PREFIX + "window") -> Interval:
        """The measured window: the host span the driver opened around it."""
        got = [s for s in self.spans if s.name == name]
        if not got:
            raise ValueError(f"no {name!r} span in the trace")
        return got[0].start, got[0].end


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------

def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    dev.append(Op(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        spans.append(Span(ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          ev.name))
    for dev in ops.values():
        dev.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return Trace(ops, spans)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of intervals as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    w0, w1 = window
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of union(a) that union(b) does not cover."""
    a, b = merge(a), merge(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# the reductions the metric readers use
# ---------------------------------------------------------------------------

def busy_ns(trace: Trace, window: Interval, device: int) -> float:
    """Time in the window in which any operation ran on ``device``."""
    return length(clip(((o.start, o.end) for o in trace.ops.get(device, [])),
                       window))


def mean_busy_ns(trace: Trace, window: Interval,
                 devices: Sequence[int]) -> float:
    return sum(busy_ns(trace, window, d) for d in devices) / len(devices)


def exposed_collective_ns(trace: Trace, window: Interval,
                          device: int) -> float:
    """Time in the window in which a collective ran on ``device`` and no
    other operation did."""
    ops = trace.ops.get(device, [])
    coll = clip(((o.start, o.end) for o in ops if o.collective), window)
    comp = clip(((o.start, o.end) for o in ops if not o.collective), window)
    return length(subtract(coll, comp))


OP_KIND = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.3 = f32[64,256]{1,0:T(8,128)} fusion(...), ...`` ->
    ``fusion.3 f32[64,256] fusion`` (a tuple result reads ``tuple``); a
    name that is not HLO text stays as it is."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    kind = OP_KIND.search(rhs)
    shape = "tuple" if rhs.startswith("(") else rhs.split(" ")[0]
    shape = shape.split("{")[0]
    return " ".join([lhs.lstrip("%"), shape] + ([kind.group(1)] if kind
                                                else []))


def op_seconds(trace: Trace, window: Interval,
               devices: Sequence[int]) -> List[Tuple[str, float]]:
    """Device time per operation (:func:`short_name`) in the window,
    averaged over the devices, largest first."""
    total: Dict[str, float] = {}
    for d in devices:
        for o in trace.ops.get(d, []):
            s, e = max(o.start, window[0]), min(o.end, window[1])
            if e > s:
                key = short_name(o.name)
                total[key] = total.get(key, 0.0) + (e - s)
    return sorted(((n, t / len(devices) * 1e-9) for n, t in total.items()),
                  key=lambda kv: -kv[1])


def idle_gaps(trace: Trace, window: Interval,
              device: int) -> List[Interval]:
    busy = merge(clip(((o.start, o.end) for o in trace.ops.get(device, [])),
                      window))
    return subtract([window], busy)


def host_label(trace: Trace, gap: Interval) -> str:
    """What the host was doing in a gap: the innermost benchmark span that
    covers the most of it, or ``host.other``."""
    best: Optional[Span] = None
    best_cover = 0.0
    for s in trace.spans:
        if s.name == HOST_PREFIX + "window":
            continue
        cover = min(s.end, gap[1]) - max(s.start, gap[0])
        if cover <= 0:
            continue
        tighter = best is not None and (s.end - s.start) < (best.end -
                                                            best.start)
        if cover > best_cover or (cover == best_cover and tighter):
            best, best_cover = s, cover
    return best.name if best is not None else "host.other"


def idle_by_host(trace: Trace, window: Interval,
                 device: int) -> List[Tuple[str, float]]:
    """Idle seconds of ``device`` in the window, by what the host was
    doing, largest first."""
    total: Dict[str, float] = {}
    for g in idle_gaps(trace, window, device):
        lab = host_label(trace, g)
        total[lab] = total.get(lab, 0.0) + (g[1] - g[0]) * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])
