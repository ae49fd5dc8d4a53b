"""Chaos harness: a process-wide fault-point registry (ISSUE 9).

Every failure mode the fault-tolerance layer claims to survive is
*injectable* — in CI, in the chaos driver (``launch/serve.py --chaos``) and
in tests — through named fault points compiled into the hot paths:

  ``ingest.apply_round``   — fired by ``IngestQueue._apply`` before the
                             fused dispatch of each round.  Arm with
                             ``exc=WorkerKilled`` to simulate the worker
                             thread dying mid-round (the kill-mid-round
                             crash of the WAL replay contract), or a
                             transient exception to exercise
                             retry/backoff.
  ``ingest.apply_lane``    — fired per lane inside the poison-excision
                             fallback; arm with ``match={"sid": s}`` to
                             poison exactly one tenant.
  ``ingest.dispatch_lane`` — fired per lane inside the DISTRIBUTED
                             per-lane dispatch loop, before that lane's
                             sharded update; arm with ``match={"sid": s}``
                             to fail a round partway through and exercise
                             the exactly-once partial-round bookkeeping
                             (landed lanes must not re-apply on retry or
                             fallback).
  ``ckpt.pre_commit``      — fired by ``checkpoint.ckpt.save`` between
                             staging the tmp dir and the atomic
                             ``os.replace``; arm with a ``handler`` to
                             tear the staged files (torn-write chaos) or
                             an ``exc`` to crash before the commit.
  ``elastic.reshard``      — fired by ``stream.elastic.reshard_stream``
                             before the hop (device-loss simulation).

Fault points are **zero-cost when disarmed**: ``fire`` is a dict lookup
returning immediately.  Arming is per-point with an optional budget
(``times``) and an optional context ``match`` so a fault can target one
sid / one step while the rest of the traffic flows.

The driver-level scenarios (kill-worker-mid-round, torn write,
restore-onto-smaller-mesh, eviction storm) live in
:func:`run_chaos_scenario`, wired to ``launch/serve.py --chaos``.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional

_ARMED: Dict[str, "_Fault"] = {}
_LOCK = threading.Lock()


class FaultInjected(RuntimeError):
    """Default exception raised by an armed fault point."""


class WorkerKilled(BaseException):
    """Simulated hard crash of a worker thread.  Deliberately a
    BaseException: it must escape the per-round ``except Exception``
    error-recording path the same way a real segfault/kill would — the
    worker dies, it does not log-and-continue."""


class _Fault:
    def __init__(self, exc=None, handler=None, times=None, match=None):
        self.exc = exc
        self.handler = handler
        self.times = times            # None = unlimited
        self.match = dict(match or {})
        self.fired = 0

    def applies(self, ctx: Dict[str, Any]) -> bool:
        if self.times is not None and self.fired >= self.times:
            return False
        return all(ctx.get(k) == v for k, v in self.match.items())


def arm(point: str, *, exc: Optional[type] = None,
        handler: Optional[Callable] = None,
        times: Optional[int] = 1,
        match: Optional[Dict[str, Any]] = None) -> None:
    """Arm ``point``.  Exactly one of ``exc`` (raised at the point) or
    ``handler`` (called with the point's context kwargs; its return value
    is ignored unless the site documents otherwise) fires per matching
    ``fire``; ``times=None`` keeps the fault armed forever."""
    if exc is None and handler is None:
        exc = FaultInjected
    with _LOCK:
        _ARMED[point] = _Fault(exc=exc, handler=handler, times=times,
                               match=match)


def disarm(point: str) -> None:
    with _LOCK:
        _ARMED.pop(point, None)


def clear() -> None:
    """Disarm everything (test teardown)."""
    with _LOCK:
        _ARMED.clear()


def armed(point: str) -> bool:
    return point in _ARMED


def fire(point: str, **ctx) -> None:
    """Hot-path hook: no-op unless ``point`` is armed and the context
    matches.  An armed ``exc`` is raised here; an armed ``handler`` runs
    here (exceptions it raises propagate — a handler may itself crash the
    site)."""
    fault = _ARMED.get(point)
    if fault is None or not fault.applies(ctx):
        return
    fault.fired += 1
    if fault.handler is not None:
        fault.handler(**ctx)
        return
    raise fault.exc(f"chaos: fault injected at {point!r} ({ctx})")


def fire_count(point: str) -> int:
    fault = _ARMED.get(point)
    return 0 if fault is None else fault.fired


# ---------------------------------------------------------------------------
# Driver-level chaos scenarios (launch/serve.py --chaos)
# ---------------------------------------------------------------------------

SCENARIOS = ("kill-worker", "torn-write", "shrink-restore", "eviction-storm")


def run_chaos_scenario(scenario: str, *, n1: int = 256, n2: int = 128,
                       r: int = 8, streams: int = 8, updates: int = 3,
                       workdir: Optional[str] = None,
                       verbose: bool = True) -> Dict[str, Any]:
    """Run one end-to-end failure-and-recovery drill; returns a result
    dict whose ``recovered`` field is the scenario's pass/fail verdict.
    A drill the process cannot stage (shrink-restore on an odd device
    count) returns ``recovered=None`` and says why under ``skipped``.

    Every scenario builds its own small serving stack, injects the fault
    through this registry (never by monkeypatching), recovers through the
    production path (WAL replay / torn-checkpoint quarantine / elastic
    restore / QoS restore) and verifies the recovery contract — bitwise
    where the contract is bitwise.
    """
    import tempfile

    import numpy as np

    out: Dict[str, Any] = {"scenario": scenario}
    say = print if verbose else (lambda *a, **k: None)
    tmp_ctx = (tempfile.TemporaryDirectory() if workdir is None else None)
    workdir = workdir if workdir is not None else tmp_ctx.name
    rng = np.random.default_rng(0)
    try:
        if scenario == "kill-worker":
            out.update(_chaos_kill_worker(rng, n1, n2, r, streams, updates,
                                          workdir, say))
        elif scenario == "torn-write":
            out.update(_chaos_torn_write(rng, n1, n2, r, workdir, say))
        elif scenario == "shrink-restore":
            out.update(_chaos_shrink_restore(say))
        elif scenario == "eviction-storm":
            out.update(_chaos_eviction_storm(rng, n1, n2, r, streams,
                                             workdir, say))
        else:
            raise ValueError(f"unknown chaos scenario {scenario!r}; "
                             f"have {SCENARIOS}")
    finally:
        clear()
        if tmp_ctx is not None:
            tmp_ctx.cleanup()
    say(f"[chaos:{scenario}] "
        + (f"not run ({out['skipped']})" if out.get("skipped")
           else f"recovered={out['recovered']}"))
    return out


def _mk_traffic(rng, streams, updates, n1, n2):
    traffic = []
    for u in range(updates):
        for s in range(streams):
            k = int(rng.integers(1, 33))
            traffic.append((s, rng.standard_normal((k, n2)).astype("float32"),
                            int(rng.integers(0, n1 - k + 1))))
    return traffic


def _chaos_kill_worker(rng, n1, n2, r, streams, updates, workdir, say):
    """Kill the ingest worker mid-round; recover by replaying the WAL into
    a fresh service — finalize must be bitwise the uninterrupted run."""
    import os
    import time

    import numpy as np

    from repro.stream import wal as wal_mod
    from repro.stream.ingest import IngestQueue, WorkerDied
    from repro.stream.service import SketchService
    from repro.stream.state import StreamConfig

    cfgs = [StreamConfig(n1=n1, n2=n2, r=r, seed=s, corange=False)
            for s in range(streams)]
    traffic = _mk_traffic(rng, streams, updates, n1, n2)

    # reference: the run that never crashes
    ref = SketchService()
    ref_sids = [ref.open(c) for c in cfgs]
    for s, H, row0 in traffic:
        ref.update(ref_sids[s], H, row0=row0)
    ref_Y = [np.asarray(ref.sketch(s)) for s in ref_sids]

    # victim: journaled ingest, worker killed mid-round
    svc = SketchService()
    sids = [svc.open(c) for c in cfgs]
    wal = wal_mod.WriteAheadLog(os.path.join(workdir, "ingest.wal"))
    q = IngestQueue(svc, wal=wal)
    # every submit of one sid lands in a distinct round, so with
    # ``updates`` submits per stream at least ``updates`` rounds run —
    # killing at round index updates-1 is guaranteed to trigger, and some
    # earlier rounds have already landed (a genuine MID-stream crash)
    kill_after = max(2, updates - 1)
    arm("ingest.apply_round", exc=WorkerKilled, times=None,
        match={"round_index": kill_after})
    died = False
    for s, H, row0 in traffic:
        try:
            q.submit(sids[s], H, row0)
        except WorkerDied:
            died = True
            break
    if not died:                     # the kill may land after the last submit
        try:
            q.flush()
        except WorkerDied:
            died = True
    say(f"[chaos] worker died={died}, wal depth={wal.depth}")
    disarm("ingest.apply_round")
    q.shutdown()
    wal.close()

    # recovery: fresh service, same stream configs, replay the journal
    t0 = time.perf_counter()
    svc2 = SketchService()
    sids2 = [svc2.open(c) for c in cfgs]
    nrec, words = wal_mod.replay(wal.path, svc2,
                                 sid_map=dict(zip(sids, sids2)))
    svc2.sync()
    dt = time.perf_counter() - t0
    bitwise = all(np.array_equal(np.asarray(svc2.sketch(s)), refy)
                  for s, refy in zip(sids2, ref_Y))
    say(f"[chaos] replayed {nrec} records / {words} words "
        f"in {dt * 1e3:.1f} ms, bitwise={bitwise}")
    return {"recovered": died and bitwise, "worker_died": died,
            "replayed_records": nrec, "replayed_words": words,
            "recover_s": dt, "bitwise": bitwise}


def _chaos_torn_write(rng, n1, n2, r, workdir, say):
    """Tear a checkpoint commit; the torn step must be quarantined, never
    restored, and the previous good step must load."""
    import os

    import numpy as np

    from repro.checkpoint import ckpt
    from repro.stream.state import StreamConfig, StreamingSketch

    d = os.path.join(workdir, "ckpt")
    st = StreamingSketch(StreamConfig(n1=n1, n2=n2, r=r, seed=3,
                                      corange=False), backend="xla")
    st.update_rows(0, rng.standard_normal((32, n2)).astype("float32"))
    st.save(d, step=1)
    good_Y = np.asarray(st.Y)
    st.update_rows(32, rng.standard_normal((32, n2)).astype("float32"))

    def tear(tmp, **_):
        os.remove(os.path.join(tmp, "manifest.json"))

    arm("ckpt.pre_commit", handler=tear)
    st.save(d, step=2)
    disarm("ckpt.pre_commit")
    torn = ckpt.torn_steps(d)
    latest = ckpt.latest_step(d)
    st2 = StreamingSketch.restore(d)
    ok = (torn == [2] and latest == 1
          and np.array_equal(np.asarray(st2.Y), good_Y))
    say(f"[chaos] torn steps={torn}, latest={latest}, "
        f"restored step-1 bitwise={ok}")
    return {"recovered": ok, "torn_steps": torn, "latest_step": latest}


def _chaos_shrink_restore(say):
    """Reshard a live stream from this process's N devices onto N/2 and
    back (N -> N/2 -> N), updating on every grid; finalize must stay
    bitwise the never-resized run.  Runs in the calling process, on its
    own devices — chips or fake CPU devices alike."""
    import jax
    import numpy as np

    from repro.core.sketch import make_grid_mesh

    from .distributed import ShardedStreamingSketch
    from .elastic import reshard_stream
    from .state import StreamConfig

    n = len(jax.devices())
    if n < 2 or n % 2:
        why = f"needs an even device count, have {n}"
        say(f"[chaos] shrink/grow not run: {why}")
        return {"recovered": None, "skipped": why, "devices": n}
    cfg = StreamConfig(n1=256, n2=256, r=8, seed=5, corange=False)
    rng = np.random.default_rng(0)
    slabs = [(i * 64, rng.standard_normal((64, 256)).astype("float32"))
             for i in range(4)]
    ref = ShardedStreamingSketch(cfg, make_grid_mesh(n, 1, 1), backend="jnp")
    for row0, H in slabs:
        ref.update_rows(row0, H)
    sk = ShardedStreamingSketch(cfg, make_grid_mesh(n, 1, 1), backend="jnp")
    for row0, H in slabs[:2]:
        sk.update_rows(row0, H)
    sk = reshard_stream(sk, (n // 2, 1, 1))     # device loss: N -> N/2
    sk.update_rows(*slabs[2])
    sk = reshard_stream(sk, (n, 1, 1))          # devices came back
    sk.update_rows(*slabs[3])
    ok = np.array_equal(np.asarray(jax.device_get(sk.Y)),
                        np.asarray(jax.device_get(ref.Y)))
    say(f"[chaos] shrink/grow {n}->{n // 2}->{n} reshard bitwise={ok}")
    return {"recovered": bool(ok), "devices": n}


def _chaos_eviction_storm(rng, n1, n2, r, streams, workdir, say):
    """Hammer a budget-1 service so every touch evicts the previous
    resident to disk; state must survive the storm bitwise."""
    import os

    import numpy as np

    from repro.stream.service import SketchService
    from repro.stream.state import StreamConfig

    cfgs = [StreamConfig(n1=n1, n2=n2, r=r, seed=s, corange=False)
            for s in range(streams)]
    ref = SketchService()
    svc = SketchService(max_resident=1,
                        spill_dir=os.path.join(workdir, "spill"))
    ref_sids = [ref.open(c) for c in cfgs]
    sids = [svc.open(c) for c in cfgs]
    for rnd in range(3):
        for i in range(streams):     # every update storms an eviction
            k = int(rng.integers(1, 33))
            H = rng.standard_normal((k, n2)).astype("float32")
            row0 = int(rng.integers(0, n1 - k + 1))
            ref.update(ref_sids[i], H, row0=row0)
            svc.update(sids[i], H, row0=row0)
    ok = all(np.array_equal(np.asarray(svc.sketch(s)),
                            np.asarray(ref.sketch(rs)))
             for s, rs in zip(sids, ref_sids))
    say(f"[chaos] {svc.stats()['evicted']} evicted after storm, "
        f"bitwise={ok}")
    return {"recovered": ok, "evicted": svc.stats()["evicted"]}
