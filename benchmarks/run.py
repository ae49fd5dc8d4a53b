"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Multi-device benchmarks run in
subprocesses with 8 fake XLA devices so this process keeps 1 device.

    PYTHONPATH=src python -m benchmarks.run [--only fig3_comm_vs_gen,...]
                                            [--smoke] [--out bench.json]
                                            [--compare BASELINE.json ...]

``--smoke`` sets REPRO_BENCH_SMOKE=1: every suite runs tiny shapes and
minimal iters (the CI bench-smoke job).  ``--out`` additionally writes the
parsed rows as JSON — the artifact CI persists as ``BENCH_<PR>.json`` so
the perf trajectory is machine-produced, not hand-pasted; the committed
trend line lives in ``benchmarks/trends/``.

``--compare A.json [B.json]`` renders a trend table.  With two paths it is
a pure post-processing mode (no suites run): A is the baseline, B the
current run.  With one path the baseline is compared against the suites
just executed.  Wall-time ratios are informational (CI runners vary);
the comparison FAILS (exit 1) only on *coverage* regressions — a suite
that existed in the baseline but is now missing, failing, or empty — or
when ``--fail-ratio`` is given and a row slows past it.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import traceback


def load_results(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def compare(baseline: dict, current: dict,
            fail_ratio: float | None = None) -> int:
    """Print a per-row trend table; return a process exit code."""
    base_suites = baseline.get("suites", {})
    cur_suites = current.get("suites", {})
    failures = []
    print(f"# trend vs baseline (smoke={baseline.get('smoke')}"
          f" -> {current.get('smoke')})")
    print("suite,row,base_us,cur_us,ratio")
    for sname, bsuite in sorted(base_suites.items()):
        csuite = cur_suites.get(sname)
        if csuite is None:
            failures.append(f"suite {sname!r} disappeared")
            continue
        if bsuite.get("ok") and not csuite.get("ok"):
            failures.append(f"suite {sname!r} now failing")
        if bsuite.get("rows") and not csuite.get("rows"):
            failures.append(f"suite {sname!r} lost all rows")
        cur_rows = {r["name"]: r for r in csuite.get("rows", [])}
        for row in bsuite.get("rows", []):
            cur = cur_rows.get(row["name"])
            if cur is None:
                print(f"{sname},{row['name']},{row['us_per_call']:.1f},"
                      f"MISSING,-")
                continue
            ratio = (cur["us_per_call"] / row["us_per_call"]
                     if row["us_per_call"] else float("inf"))
            print(f"{sname},{row['name']},{row['us_per_call']:.1f},"
                  f"{cur['us_per_call']:.1f},{ratio:.2f}")
            # zero/degenerate baselines carry no trend signal: report the
            # inf ratio but never fail on it
            if (fail_ratio is not None and row["us_per_call"] > 0
                    and ratio > fail_ratio):
                failures.append(
                    f"{sname}/{row['name']} slowed {ratio:.2f}x "
                    f"(> {fail_ratio}x)")
    for sname in sorted(set(cur_suites) - set(base_suites)):
        for row in cur_suites[sname].get("rows", []):
            print(f"{sname},{row['name']},NEW,{row['us_per_call']:.1f},-")
    if failures:
        print(f"# trend compare FAILED: {failures}", file=sys.stderr)
        return 1
    print("# trend compare OK", file=sys.stderr)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shapes smoke mode (REPRO_BENCH_SMOKE=1)")
    ap.add_argument("--out", default=None,
                    help="write suite rows as JSON to this path")
    ap.add_argument("--compare", nargs="+", default=None, metavar="JSON",
                    help="baseline JSON (and optionally a current JSON for "
                         "pure post-processing) to trend-compare against")
    ap.add_argument("--fail-ratio", type=float, default=None,
                    help="fail when a row slows past this ratio "
                         "(default: wall times informational only)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="run the in-process suites under the repro.obs "
                         "tracer+ledger and write a Chrome trace_event "
                         "JSON to FILE (plus the honesty report to stderr)")
    args = ap.parse_args()
    if args.compare and len(args.compare) > 2:
        ap.error("--compare takes at most two JSON paths")
    if args.compare and len(args.compare) == 2:
        # pure post-processing: baseline vs an existing result file
        sys.exit(compare(load_results(args.compare[0]),
                         load_results(args.compare[1]),
                         args.fail_ratio))
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    from repro.launch import compile_cache
    compile_cache.enable()

    # import after --smoke is in the environment so suites (and their
    # subprocess snippets) all observe the same mode
    from . import (bench_bounds, bench_comm_vs_gen, bench_error,
                   bench_grad_compress, bench_kernels, bench_nystrom,
                   bench_plan, bench_sketch, bench_stream)

    suites = {
        "thm_bounds": bench_bounds.main,        # Thm 2/3 tables
        "fig3_comm_vs_gen": bench_comm_vs_gen.main,
        "fig4_scaling": bench_sketch.main,
        "fig5-8_nystrom": bench_nystrom.main,
        "tab2_error": bench_error.main,
        "kernels": bench_kernels.main,
        "grad_compress": bench_grad_compress.main,
        "stream": bench_stream.main,
        "plan": bench_plan.main,                # predicted vs measured + tune
    }

    tracer = ledger = None
    if args.trace:
        # in-process suites only: subprocess benchmarks (fake multi-device
        # harnesses) run outside this tracer's process
        from repro import obs
        tracer, ledger, _ = obs.install_observability()

    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = []
    results = {}
    for name, fn in suites.items():
        if only and name not in only:
            continue
        buf = io.StringIO()
        err = None
        try:
            with contextlib.redirect_stdout(buf):
                fn()
        except Exception as e:  # noqa: BLE001
            err = e
            failed.append((name, e))
        text = buf.getvalue()
        sys.stdout.write(text)
        if err is not None:
            traceback.print_exception(err)
        ok = err is None
        rows = []
        for line in text.splitlines():
            parts = line.split(",", 2)
            if len(parts) == 3:
                try:
                    us = float(parts[1])
                except ValueError:
                    continue
                rows.append({"name": parts[0], "us_per_call": us,
                             "derived": parts[2]})
        results[name] = {"ok": ok, "rows": rows}

    if args.trace:
        from repro import obs
        tracer.export_chrome(args.trace)
        print(f"# trace written to {args.trace} ({len(tracer.spans)} spans)",
              file=sys.stderr)
        if len(ledger):
            print(obs.honesty_report(ledger), file=sys.stderr)
        obs.uninstall_observability()

    payload = {"schema": 1, "smoke": args.smoke, "suites": results}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {args.out}", file=sys.stderr)

    rc = 0
    if args.compare:
        rc = compare(load_results(args.compare[0]), payload,
                     args.fail_ratio)

    if failed:
        print(f"# {len(failed)} suites FAILED: {[n for n, _ in failed]}",
              file=sys.stderr)
        sys.exit(1)
    if rc:
        sys.exit(rc)


if __name__ == "__main__":
    main()
