"""Analyze saved telemetry traces with the step perf engine
(alpa_tpu.telemetry.perf, ISSUE 9).

Usage::

    python scripts/perf_tool.py analyze      TRACE.json [--json] [--top N]
    python scripts/perf_tool.py critical-path TRACE.json [--top K]
    python scripts/perf_tool.py whatif       TRACE.json [--zero reshard]
                                             [--name SUBSTR]
    python scripts/perf_tool.py compare      A.json B.json
    python scripts/perf_tool.py drift        [TRACE.json] [--top N] [--json]
    python scripts/perf_tool.py superopt     [--dir DIR] [--all] [--json]

``superopt`` prints every accepted certified-superoptimization rewrite
decision found in the compile cache's disk tier (ISSUE 17; the engine
caches accepted rewrites under the ``superopt`` namespace at compile
time) — before/after simulated critical path and peak bytes, the
rewritten-plan fingerprint, and the admissible-candidate search log.
The cache directory comes from ``--dir``, else ``ALPA_TPU_CACHE_DIR``.
For the verdict side of a rewrite (which findings the gate compared),
``scripts/verify_tool.py verify diff`` diffs two cached verdicts with
the same ``(analysis, code)``-set semantics the acceptance gate uses.

``analyze`` prints the full :class:`StepPerfReport` (critical path,
per-mesh bubble fractions, transfer overlap, stage MFU where RUN spans
carry stage names) for the last ``pipeshard.step`` envelope in the
trace, and says on its first line which clock it read: a saved Chrome
trace holds the driver's spans only, so it is the host's, where a RUN's
time is its enqueue's (``get_perf_report(capture)`` in the process reads
the device's); ``critical-path`` prints just the path table; ``whatif``
re-simulates the step with an op class made free ("if this RESHARD were
free, step −X%"); ``compare`` diffs two analyzed traces metric by
metric (the interactive sibling of ``benchmark/perf_gate.py``, which
does the same against committed baselines with tolerances); ``drift``
prints the measured-cost calibration store's worst modeled-vs-measured
divergences (ISSUE 12) — pass a trace to ingest it first, or point
``ALPA_TPU_CALIBRATION_DIR`` at a persisted store.

Traces come from ``scripts/trace_tool.py record``, from
``ALPA_TPU_TRACE_DIR`` auto-saves, or from ``dump_debug_info``'s
``trace.json``.  Offline analysis has no lowered program to join
against, so dependencies are per-track order (the report says so);
in-process callers get the dataflow-graph join via
``PipeshardDriverExecutable.get_perf_report()``.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from alpa_tpu.telemetry import perf as _perf  # noqa: E402


def _load(path):
    with open(path, encoding="utf-8") as f:
        trace = json.load(f)
    if "traceEvents" not in trace:
        sys.exit(f"{path}: not a chrome trace (no traceEvents)")
    return trace


def _report(path):
    report = _perf.report_from_trace(_load(path))
    if report is None:
        sys.exit(f"{path}: no analyzable step (no mesh-track "
                 f"instruction/transfer spans)")
    return report


def cmd_analyze(args):
    report = _report(args.trace)
    if args.json:
        print(json.dumps(report.to_dict(), indent=1))
    else:
        print(report.format_text(top=args.top))


def cmd_critical_path(args):
    report = _report(args.trace)
    print(report.critical_path.format_table(top=args.top))
    by_kind = report.critical_path.by_kind()
    if by_kind:
        parts = ", ".join(f"{k} {us:.1f} us"
                          for k, us in sorted(by_kind.items()))
        print(f"path op time by kind: {parts}")


def cmd_whatif(args):
    report = _report(args.trace)
    verdict = report.whatif(args.zero, name_substr=args.name)
    print(json.dumps(verdict, indent=1))
    what = verdict["zero"]
    print(f"if every {what} op were free: step "
          f"{verdict['baseline_us']:.1f} us -> "
          f"{verdict['whatif_us']:.1f} us "
          f"(-{100.0 * verdict['saving_fraction']:.1f}%, "
          f"{verdict['n_zeroed']} ops zeroed)", file=sys.stderr)


def _flatten(d, prefix=""):
    out = {}
    for k, v in d.items():
        key = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            out.update(_flatten(v, key))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    return out


def _metrics_from(path):
    """Flattened metrics from a chrome trace, an ``analyze --json``
    report dict, or a perf_gate baseline file."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if "traceEvents" in data:
        return _flatten(_report(path).to_dict())
    if "metrics" in data:            # perf_gate baseline format
        return {k: v["value"] for k, v in data["metrics"].items()
                if isinstance(v, dict) and "value" in v}
    return _flatten(data)


def cmd_compare(args):
    a = _metrics_from(args.a)
    b = _metrics_from(args.b)
    keys = sorted(set(a) & set(b))
    print(f"{'metric':<48} {'a':>12} {'b':>12} {'ratio':>8}")
    for k in keys:
        ratio = b[k] / a[k] if a[k] else float("inf") if b[k] else 1.0
        flag = "  <--" if ratio > 1.25 or ratio < 0.8 else ""
        print(f"{k:<48} {a[k]:>12.4f} {b[k]:>12.4f} "
              f"{ratio:>8.3f}{flag}")
    only_a = sorted(set(a) - set(b))
    only_b = sorted(set(b) - set(a))
    if only_a:
        print(f"only in {args.a}: {', '.join(only_a)}")
    if only_b:
        print(f"only in {args.b}: {', '.join(only_b)}")


def cmd_drift(args):
    from alpa_tpu.telemetry import calibration as _cal
    store = _cal.get_calibration_store()
    if args.trace:
        ingested = _cal.ingest_chrome_trace(_load(args.trace),
                                            store=store)
        print(f"ingested {sum(ingested.values())} samples over "
              f"{len(ingested)} signatures from {args.trace}",
              file=sys.stderr)
    if args.json:
        print(json.dumps(_cal.drift_table(store, top=args.top),
                         indent=1))
    else:
        print(_cal.format_calibration_report(store))


def cmd_superopt(args):
    from alpa_tpu.analysis import superopt as _superopt
    cache = None
    if args.dir:
        from alpa_tpu.compile_cache import CompileCache
        cache = CompileCache(cache_dir=args.dir)
    cached = _superopt.load_cached_decisions(cache)
    if not cached:
        where = args.dir or os.environ.get("ALPA_TPU_CACHE_DIR") or (
            "(memory only — set ALPA_TPU_CACHE_DIR)")
        sys.exit(f"no cached superopt decisions in {where}; accepted "
                 f"rewrites are cached at compile time when "
                 f"superopt_mode != off")
    shown = cached if args.all else cached[:1]
    if args.json:
        print(json.dumps({"schema": "alpa-superopt/v1",
                          "decisions": [{"key": e["key"],
                                         "mtime": e["mtime"],
                                         **e["decision"]}
                                        for e in shown]},
                         indent=2, sort_keys=True, default=str))
        return
    for e in shown:
        d = e["decision"]
        base_peak = sum(d.get("baseline_peak_bytes", ()))
        peak = sum(d.get("peak_bytes", ()))
        print(f"== superopt {e['key'][:16]}.. ==")
        print(f"  baseline plan: {d.get('baseline_fingerprint', '?')[:16]}"
              f"  rewritten plan: {d.get('fingerprint', '?')[:16]}")
        print(f"  simulated critical path: "
              f"{d.get('baseline_makespan_us', 0.0):.1f} -> "
              f"{d.get('makespan_us', 0.0):.1f} us")
        print(f"  simulated peak bytes:    {base_peak:.0f} -> "
              f"{peak:.0f}")
        n_rewrites = sum(1 for i, x in enumerate(d.get("layout", ()))
                         if not isinstance(x, int) or x != i)
        print(f"  non-identity layout entries: {n_rewrites}")
        for entry in d.get("log", ())[-10:]:
            print(f"    {entry.get('family', '?'):<16} makespan "
                  f"{entry.get('makespan_us', 0.0):.1f} us, peak "
                  f"{entry.get('peak_bytes', 0.0):.0f} B")
        print()
    if not args.all and len(cached) > 1:
        print(f"({len(cached) - 1} older decision(s) cached; "
              f"--all to show)")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    pa = sub.add_parser("analyze", help="full step perf report")
    pa.add_argument("trace")
    pa.add_argument("--json", action="store_true",
                    help="machine-readable report dict")
    pa.add_argument("--top", type=int, default=10)
    pa.set_defaults(func=cmd_analyze)

    pc = sub.add_parser("critical-path",
                        help="just the measured critical path")
    pc.add_argument("trace")
    pc.add_argument("--top", type=int, default=10)
    pc.set_defaults(func=cmd_critical_path)

    pw = sub.add_parser("whatif",
                        help="re-simulate with an op class made free")
    pw.add_argument("trace")
    pw.add_argument("--zero", default="reshard",
                    choices=("reshard", "transfer", "run", "free"))
    pw.add_argument("--name", default=None,
                    help="zero ops whose name contains SUBSTR instead")
    pw.set_defaults(func=cmd_whatif)

    pp = sub.add_parser("compare",
                        help="diff two analyzed traces metric by metric")
    pp.add_argument("a")
    pp.add_argument("b")
    pp.set_defaults(func=cmd_compare)

    pd = sub.add_parser(
        "drift", help="worst modeled-vs-measured cost divergences from "
        "the calibration store (ISSUE 12)")
    pd.add_argument("trace", nargs="?", default=None,
                    help="optional chrome trace to ingest first")
    pd.add_argument("--top", type=int, default=0,
                    help="show only the N worst entries (0 = all)")
    pd.add_argument("--json", action="store_true",
                    help="machine-readable drift table")
    pd.set_defaults(func=cmd_drift)

    ps = sub.add_parser(
        "superopt", help="cached certified-superoptimization rewrite "
        "decisions: before/after simulated cost + accepted rewrite "
        "log (ISSUE 17)")
    ps.add_argument("--dir", default=None,
                    help="compile cache dir (default ALPA_TPU_CACHE_DIR)")
    ps.add_argument("--all", action="store_true",
                    help="show every cached decision, not just newest")
    ps.add_argument("--json", action="store_true",
                    help="machine-readable decisions")
    ps.set_defaults(func=cmd_superopt)

    args = p.parse_args(argv)
    args.func(args)


if __name__ == "__main__":
    main()
