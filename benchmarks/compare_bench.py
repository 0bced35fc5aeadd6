"""CI perf-regression gate: diff a fresh --smoke benchmark report against
the committed baseline and fail on large per-entry slowdowns.

Gated metrics are the wall-clock fields this repo's perf story is built on
(``fused_ms`` from ``BENCH_kernels.json``,
``pipelined_ms`` from ``BENCH_dualcore.json``, ``p50_ms`` / ``p95_ms``
request latencies from ``BENCH_serving.json`` / ``BENCH_fleet.json``),
plus two higher-is-better fields: ``aggregate_fps`` from
``BENCH_fleet.json`` (the multi-network throughput claim) and
``goodput_fps`` from ``BENCH_chaos.json`` (in-SLO throughput under
injected faults), which fail when fresh drops below baseline /
threshold.  Baseline-leg timings
(unfused, sequential) and the remaining throughput fields (fps,
tokens/s) are deliberately *not* gated — a slower baseline is not a
regression.  Entries present on only one side are
reported but never fail the gate (shapes come and go as benches evolve).

    python -m benchmarks.compare_bench \
        --baseline BENCH_kernels.json --fresh /tmp/fresh.json \
        [--threshold 2.0] [--min-ms 1.0]

Exit status 1 iff any entry slowed down by more than ``--threshold`` x
(entries whose baseline is below ``--min-ms`` are skipped: micro-timings
are dominated by dispatch noise).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

GATED_FIELDS = ("fused_ms", "pipelined_ms", "p50_ms", "p95_ms")
GATED_HIGHER_FIELDS = ("aggregate_fps",        # regression = fresh DROPS
                       "goodput_fps")


def _is_higher_better(key: str) -> bool:
    return key.rsplit("/", 1)[-1] in GATED_HIGHER_FIELDS


@dataclasses.dataclass
class Regression:
    key: str
    baseline: float
    fresh: float

    @property
    def ratio(self) -> float:
        return self.fresh / self.baseline if self.baseline else float("inf")


def extract_metrics(report: dict) -> dict[str, float]:
    """Flatten a benchmark report to ``path -> gated metric``.  List items
    are keyed by their ``shape`` field when present (stable under
    reordering), else by index."""
    out: dict[str, float] = {}

    def walk(node, path: list[str]):
        if isinstance(node, dict):
            for k, v in node.items():
                if (k in GATED_FIELDS or k in GATED_HIGHER_FIELDS) \
                        and isinstance(v, (int, float)):
                    out["/".join(path + [k])] = float(v)
                elif isinstance(v, (dict, list)):
                    walk(v, path + [k])
        elif isinstance(node, list):
            for i, v in enumerate(node):
                label = (v.get("shape") if isinstance(v, dict) else None)
                walk(v, path + [str(label) if label else str(i)])

    walk(report, [])
    return out


def compare(baseline: dict, fresh: dict, threshold: float = 2.0,
            min_ms: float = 1.0) -> tuple[list[Regression], list[str]]:
    """Return (regressions beyond ``threshold``x, informational notes)."""
    base_m = extract_metrics(baseline)
    fresh_m = extract_metrics(fresh)
    regressions: list[Regression] = []
    notes: list[str] = []
    for key in sorted(base_m.keys() | fresh_m.keys()):
        if key not in base_m:
            notes.append(f"new entry (not gated): {key}")
            continue
        if key not in fresh_m:
            notes.append(f"entry disappeared (not gated): {key}")
            continue
        b, f = base_m[key], fresh_m[key]
        if _is_higher_better(key):
            # throughput: fresh falling below baseline/threshold fails
            if b <= 0:
                notes.append(f"skipped (non-positive baseline): {key}")
            elif f * threshold < b:
                regressions.append(Regression(key, b, f))
            else:
                notes.append(f"ok ({f / b:5.2f}x, higher-better): {key} "
                             f"[{b:.2f} -> {f:.2f}]")
            continue
        if b < min_ms:
            notes.append(f"skipped (baseline {b:.3f} ms < {min_ms} ms "
                         f"noise floor): {key}")
            continue
        if f > threshold * b:
            regressions.append(Regression(key, b, f))
        else:
            notes.append(f"ok ({f / b:5.2f}x): {key} "
                         f"[{b:.2f} -> {f:.2f} ms]")
    return regressions, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", required=True,
                    help="committed baseline JSON")
    ap.add_argument("--fresh", required=True,
                    help="freshly measured JSON from this run")
    ap.add_argument("--threshold", type=float, default=2.0,
                    help="fail on fresh > threshold x baseline (default 2)")
    ap.add_argument("--min-ms", type=float, default=1.0,
                    help="ignore entries whose baseline is below this")
    args = ap.parse_args(argv)

    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.fresh) as f:
        fresh = json.load(f)

    regressions, notes = compare(baseline, fresh, args.threshold,
                                 args.min_ms)
    for n in notes:
        print(f"  {n}")
    if regressions:
        print(f"\nPERF GATE FAILED: {len(regressions)} entr"
              f"{'y' if len(regressions) == 1 else 'ies'} regressed "
              f"beyond {args.threshold}x vs baseline ({args.baseline}):")
        for r in regressions:
            if _is_higher_better(r.key):
                print(f"  {r.ratio:5.2f}x  {r.key}  "
                      f"[{r.baseline:.2f} -> {r.fresh:.2f}, "
                      f"higher-is-better: throughput DROPPED]")
            else:
                print(f"  {r.ratio:5.2f}x  {r.key}  "
                      f"[{r.baseline:.2f} -> {r.fresh:.2f} ms]")
        return 1
    print(f"\nperf gate OK: {len(extract_metrics(baseline))} baseline "
          f"entries within {args.threshold}x")
    return 0


if __name__ == "__main__":
    sys.exit(main())
