"""Gate: fresh values against committed ones (ISSUE 9).

Compares a flat metric dict against
``benchmark/results/perf_gate_baseline.json``, which names each gated
metric with its committed value and tolerance::

    {"metrics": {
        "critical_path_us":  {"value": 596.0, "max_ratio": 1.05},
        "modelcheck.states": {"value": 22.0, "max_ratio": 1.0,
                              "min_ratio": 1.0}
    }}

The baseline holds no time measured on a machine: its entries are the
values ``alpa_tpu.telemetry.perf`` computes from the committed fixture
trace, and the exact counts (states explored, terms, error bounds,
byte ratios) that tier-1 tests pass to :func:`gate`.  Speed is measured by ``chipbench/``
on the chip and recorded in ``PERF_LEDGER.jsonl``, not here.

``max_ratio`` bounds fresh/baseline above; optional ``min_ratio`` bounds
it below; optional ``max_abs`` is an absolute ceiling.  Only metrics
present in BOTH the fresh dict and the baseline are checked, so one
baseline serves every caller.  The verdict is machine-readable and every
run increments ``alpa_perf_gate_total{result}`` in the central registry.

Usage::

    python benchmark/perf_gate.py --trace TRACE.json [--baseline FILE]
                                  [--update]

Exit status 0 = pass, 1 = fail.  ``--update`` rewrites the baseline's
values from the fresh run (tolerances preserved) instead of checking.
"""
import argparse
import json
import os
import sys
from typing import Any, Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "benchmark", "results",
                                "perf_gate_baseline.json")
FIXTURE_TRACE = os.path.join(REPO, "benchmark", "results",
                             "perf_gate_fixture_trace.json")


def flatten_metrics(d: Dict[str, Any], prefix: str = ""
                    ) -> Dict[str, float]:
    """Nested report dict -> flat {dotted.name: float} (bools excluded)."""
    out: Dict[str, float] = {}
    for k, v in d.items():
        key = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten_metrics(v, key))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    return out


def check(fresh: Dict[str, float],
          baseline: Dict[str, Any]) -> Dict[str, Any]:
    """Gate ``fresh`` against the baseline spec; returns the verdict."""
    checks = []
    specs = baseline.get("metrics", {})
    for name, spec in sorted(specs.items()):
        if name not in fresh:
            continue
        base_val = float(spec["value"])
        fresh_val = fresh[name]
        ratio = (fresh_val / base_val) if base_val else (
            1.0 if fresh_val == 0 else float("inf"))
        ok = True
        reasons = []
        max_ratio = spec.get("max_ratio")
        if max_ratio is not None and ratio > float(max_ratio):
            ok = False
            reasons.append(f"ratio {ratio:.3f} > max_ratio {max_ratio}")
        min_ratio = spec.get("min_ratio")
        if min_ratio is not None and ratio < float(min_ratio):
            ok = False
            reasons.append(f"ratio {ratio:.3f} < min_ratio {min_ratio}")
        max_abs = spec.get("max_abs")
        if max_abs is not None and fresh_val > float(max_abs):
            ok = False
            reasons.append(f"value {fresh_val:.4f} > max_abs {max_abs}")
        checks.append({
            "metric": name,
            "baseline": base_val,
            "fresh": round(fresh_val, 4),
            "ratio": round(ratio, 4),
            "ok": ok,
            **({"reason": "; ".join(reasons)} if reasons else {}),
        })
    n_failed = sum(1 for c in checks if not c["ok"])
    return {
        "pass": n_failed == 0 and bool(checks),
        "n_checked": len(checks),
        "n_failed": n_failed,
        "n_skipped": len(specs) - len(checks),
        "checks": checks,
    }


def gate(fresh: Dict[str, float],
         baseline_path: str = DEFAULT_BASELINE) -> Dict[str, Any]:
    """Load the baseline, run :func:`check`, record the verdict in the
    metrics registry (``alpa_perf_gate_total{result}``)."""
    with open(baseline_path, encoding="utf-8") as f:
        baseline = json.load(f)
    verdict = check(fresh, baseline)
    from alpa_tpu.telemetry.perf import record_gate_verdict
    record_gate_verdict(verdict["pass"])
    return verdict


def _fresh_from_trace(path: str) -> Dict[str, float]:
    from alpa_tpu.telemetry.perf import report_from_trace
    with open(path, encoding="utf-8") as f:
        trace = json.load(f)
    report = report_from_trace(trace)
    if report is None:
        sys.exit(f"{path}: no analyzable step in trace")
    return flatten_metrics(report.to_dict())


def _update(fresh: Dict[str, float], baseline_path: str):
    if os.path.exists(baseline_path):
        with open(baseline_path, encoding="utf-8") as f:
            baseline = json.load(f)
    else:
        baseline = {"metrics": {}}
    metrics = baseline.setdefault("metrics", {})
    for name, spec in metrics.items():
        if name in fresh:
            spec["value"] = round(fresh[name], 4)
    with open(baseline_path, "w", encoding="utf-8") as f:
        json.dump(baseline, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"updated {len([n for n in metrics if n in fresh])} baseline "
          f"value(s) in {baseline_path}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--trace", default=FIXTURE_TRACE,
                   help="chrome trace to analyze (default: the "
                        "committed fixture)")
    p.add_argument("--baseline", default=DEFAULT_BASELINE)
    p.add_argument("--update", action="store_true",
                   help="rewrite baseline values from this run instead "
                        "of gating")
    args = p.parse_args(argv)

    fresh = _fresh_from_trace(args.trace)
    if args.update:
        _update(fresh, args.baseline)
        return 0
    verdict = gate(fresh, args.baseline)
    print(json.dumps(verdict, indent=1))
    if not verdict["pass"]:
        failed = [c["metric"] for c in verdict["checks"] if not c["ok"]]
        print(f"PERF GATE FAILED: {verdict['n_failed']}/"
              f"{verdict['n_checked']} checks "
              f"({', '.join(failed) or 'no metrics checked'})",
              file=sys.stderr)
        return 1
    print(f"perf gate passed: {verdict['n_checked']} checks, "
          f"{verdict['n_skipped']} baseline metric(s) not measured "
          f"this run", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
