"""Serving driver for the repo's two request workloads.

LM decoding (continuous-batching-lite):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b \
      --requests 6 --max-new 16

Multi-tenant sketch ingest (shape-bucketed ragged batching behind the
bounded async queue):

  PYTHONPATH=src python -m repro.launch.serve --workload sketch \
      --streams 64 --updates 4 --n1 1024 --n2 512 --r 32

Chaos harness (stream/faults.py): inject a named failure scenario into
the serving stack and verify the recovery contract end to end —
kill-worker (WAL replay, bitwise), torn-write (checkpoint quarantine),
shrink-restore (live mesh resize N -> N/2 -> N on this process's own
devices, bitwise finalize; needs an even device count — the chips of a
2x2 host, or XLA_FLAGS=--xla_force_host_platform_device_count=8 on a
CPU), eviction-storm:

  PYTHONPATH=src python -m repro.launch.serve --chaos kill-worker
  PYTHONPATH=src python -m repro.launch.serve --chaos all
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.models import get_api
from repro.obs import trace as obs_trace
from repro.serve.engine import BatchedServer, Request


def run_lm(args):
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    api = get_api(cfg)
    params = api.init(jax.random.key(0), cfg)

    server = BatchedServer(params, cfg, slots=args.slots,
                           max_len=args.max_len, eos=-1)
    for i in range(args.requests):
        server.submit(Request(rid=i, prompt=[2 + i, 5, 7],
                              max_new=args.max_new))
    t0 = time.time()
    server.run()
    dt = time.time() - t0
    print(f"[serve] {args.requests} requests on {args.slots} slots "
          f"in {dt:.1f}s")
    return server


def run_sketch(args):
    """Drive N concurrent sketch streams through the async ingest queue
    and report sustained throughput + tail latency."""
    import numpy as np

    from repro.serve.engine import make_ingest_queue, make_sketch_service
    from repro.stream.state import StreamConfig

    rng = np.random.default_rng(0)
    svc = make_sketch_service(max_resident=args.max_resident or None)
    sids = [svc.open(StreamConfig(n1=args.n1, n2=args.n2, r=args.r, seed=s))
            for s in range(args.streams)]
    ks = [int(rng.integers(1, args.max_rows + 1))
          for _ in range(args.streams * args.updates)]
    q = make_ingest_queue(svc, depth=args.depth, window=args.window,
                          expected_ks=ks)
    # startup warmup on throwaway streams: compile every (bucket height,
    # pow2 lane count) pair live traffic can produce — partial drains give
    # arbitrary per-bucket occupancies, so enumerate counts exactly the
    # way a real server warms its shape set before taking traffic
    from repro.stream import snap_bucket
    tmp = [svc.open(StreamConfig(n1=args.n1, n2=args.n2, r=args.r,
                                 seed=1_000_000 + s))
           for s in range(args.streams)]
    tops = sorted({snap_bucket(k, q.bucket_edges) for k in ks})
    for kb in tops:
        c = 1
        while c <= args.streams:
            svc.update_ragged(
                [(tmp[i], np.zeros((kb, args.n2), np.float32), 0)
                 for i in range(c)], bucket_edges=q.bucket_edges)
            c *= 2
    svc.sync()
    for t in tmp:
        svc.close(t)
    print(f"[serve:sketch] warmed {svc.stats()['compiled_updates']} "
          f"programs over buckets {tops}")
    t0 = time.perf_counter()
    it = iter(ks)
    for u in range(args.updates):
        # submit under a round span: the queue worker's apply spans
        # stitch under it cross-thread in the exported trace
        with obs_trace.span("client.update_round", cat="client", round=u):
            for sid in sids:
                k = next(it)
                H = rng.standard_normal((k, args.n2)).astype(np.float32)
                q.submit(sid, H, int(rng.integers(0, args.n1 - k + 1)))
    q.flush(raise_errors=True)
    dt = time.perf_counter() - t0
    st = q.stats()
    n = args.streams * args.updates
    print(f"[serve:sketch] {n} updates over {args.streams} streams in "
          f"{dt:.2f}s — {n / dt:.1f} updates/s, p50 "
          f"{st['latency_p50_s'] * 1e3:.1f} ms, p99 "
          f"{st['latency_p99_s'] * 1e3:.1f} ms, pad waste "
          f"{st['pad_waste']:.1%}, {st['rounds']} fused rounds")
    q.shutdown()
    return st


def run_chaos(args):
    """Run one (or all) chaos scenarios and report the recovery verdicts.
    Exits non-zero if any scenario that ran failed to recover; a drill
    this process cannot stage is reported NOT RUN."""
    from repro.stream import faults

    names = list(faults.SCENARIOS) if args.chaos == "all" else [args.chaos]
    results = {}
    for name in names:
        print(f"[chaos] scenario {name!r} ...")
        res = faults.run_chaos_scenario(
            name, streams=min(args.streams, 8), updates=args.updates)
        results[name] = res
        verdict = ("NOT RUN" if res.get("skipped") else
                   "RECOVERED" if res.get("recovered") else "FAILED")
        print(f"[chaos] {name}: {verdict} "
              f"{ {k: v for k, v in res.items() if k != 'recovered'} }")
    if not all(r.get("recovered") or r.get("skipped")
               for r in results.values()):
        raise SystemExit(1)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "sketch"), default="lm")
    ap.add_argument("--chaos", metavar="SCENARIO", default=None,
                    help="run a stream/faults.py chaos scenario instead of "
                         "a workload: kill-worker | torn-write | "
                         "shrink-restore | eviction-storm | all")
    # lm
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    # sketch
    ap.add_argument("--streams", type=int, default=64)
    ap.add_argument("--updates", type=int, default=4,
                    help="updates per stream")
    ap.add_argument("--n1", type=int, default=1024)
    ap.add_argument("--n2", type=int, default=512)
    ap.add_argument("--r", type=int, default=32)
    ap.add_argument("--max-rows", type=int, default=64,
                    help="lane heights drawn from [1, max-rows]")
    ap.add_argument("--depth", type=int, default=256)
    ap.add_argument("--window", type=int, default=64)
    ap.add_argument("--max-resident", type=int, default=0,
                    help="admission budget (0 = unlimited)")
    # observability (repro.obs)
    ap.add_argument("--metrics", action="store_true",
                    help="dump the Prometheus text exposition of the "
                         "process metrics registry after the run")
    ap.add_argument("--trace-out", metavar="FILE", default=None,
                    help="write a Chrome/Perfetto trace (trace_event JSON) "
                         "of the run to FILE; also prints the comm-ledger "
                         "honesty report")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    tracing = args.trace_out is not None
    if tracing:
        from repro import obs
        tracer, ledger, _ = obs.install_observability()
    try:
        if args.chaos is not None:
            out = run_chaos(args)
        else:
            out = (run_sketch(args) if args.workload == "sketch"
                   else run_lm(args))
    finally:
        if tracing:
            tracer.export_chrome(args.trace_out)
            print(f"[serve] trace written to {args.trace_out} "
                  f"({len(tracer.spans)} spans)")
            if len(ledger):
                print(obs.honesty_report(ledger))
            obs.uninstall_observability()
        if args.metrics:
            from repro.obs import get_metrics
            print(get_metrics().prometheus_text(), end="")
    return out


if __name__ == "__main__":
    main()
