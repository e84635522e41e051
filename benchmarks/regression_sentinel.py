"""Bench regression sentinel CLI: exit nonzero when the newest BENCH
record regresses the history.

Loads the repo's ``BENCH_r*.json`` perf trajectory, fits noise-tolerant
per-metric baselines (median + MAD-widened tolerance band,
higher/lower-is-better aware — ``photon_ml_tpu.obs.sentinel``) on every
record EXCEPT the one under test, and checks the current record against
them. Designed for two call shapes:

    # CI / standalone: gate the newest record against its predecessors
    python benchmarks/regression_sentinel.py

    # gate an arbitrary record (e.g. a fresh `python bench.py` output
    # saved to a file) against the committed history
    python benchmarks/regression_sentinel.py --current my_record.json

``bench.py --sentinel`` runs the same check in-process on the record it
just produced. Exit codes: 0 = within tolerance, 1 = regression(s),
2 = not enough history to fit a single baseline.

Untracked metrics (fetch latency, phase walls, registry snapshots) and
metrics new to the current record are tolerated by construction — the
sentinel gates performance, not growth.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

# runnable as `python benchmarks/regression_sentinel.py` from anywhere
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

from photon_ml_tpu.obs import sentinel as _sentinel  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="gate a BENCH record against the BENCH_r*.json history"
    )
    p.add_argument(
        "--history", default=os.path.join(_REPO_ROOT, "BENCH_r*.json"),
        help="glob of BENCH history files (default: repo BENCH_r*.json)",
    )
    p.add_argument(
        "--current", default=None,
        help="record to gate: a BENCH_*.json wrapper or a bare bench.py "
        "JSON line file (default: the newest history file, which is then "
        "excluded from the baseline fit)",
    )
    p.add_argument(
        "--tolerance", type=float, default=_sentinel.DEFAULT_TOLERANCE,
        help="relative tolerance floor for every tracked metric",
    )
    p.add_argument(
        "--mad-k", type=float, default=_sentinel.DEFAULT_MAD_K,
        help="how many history MADs widen a metric's band",
    )
    p.add_argument(
        "--min-samples", type=int, default=_sentinel.DEFAULT_MIN_SAMPLES,
        help="history records a metric needs before it is gated",
    )
    p.add_argument(
        "--list", action="store_true",
        help="print every fitted baseline, then the verdict",
    )
    args = p.parse_args(argv)

    history = sorted(glob.glob(args.history))
    current_path = args.current
    if current_path is None:
        if not history:
            print(
                f"sentinel: no history matches {args.history!r}",
                file=sys.stderr,
            )
            return 2
        current_path = history[-1]
    # never fit the record under test into its own baseline
    history = [
        h for h in history
        if os.path.abspath(h) != os.path.abspath(current_path)
    ]
    current = _sentinel.load_bench_record(current_path)
    if current is None:
        print(
            f"sentinel: {current_path!r} has no parseable record",
            file=sys.stderr,
        )
        return 2

    regs, baselines, n_hist = _sentinel.run_sentinel(
        history,
        current,
        min_samples=args.min_samples,
        tolerance=args.tolerance,
        mad_k=args.mad_k,
    )
    if not baselines:
        print(
            f"sentinel: no metric reached {args.min_samples} samples over "
            f"{n_hist} history record(s); nothing to gate",
            file=sys.stderr,
        )
        return 2

    if args.list:
        for name in sorted(baselines):
            b = baselines[name]
            direction = "higher" if b.direction > 0 else "lower"
            print(
                f"  {name}: median {b.median:g} ({direction} is better, "
                f"band ±{b.tol:.0%}, n={b.n_samples})",
                file=sys.stderr,
            )

    print(
        json.dumps(
            {
                "metric": "bench_regression_sentinel",
                "value": len(regs),
                "unit": "regressions",
                "vs_baseline": len(baselines),
                "extra": {
                    "current": os.path.basename(current_path),
                    "history_records": n_hist,
                    "tracked_metrics": len(baselines),
                    "regressions": [
                        {
                            "metric": r.metric,
                            "current": r.current,
                            "median": r.baseline.median,
                            "bound": r.baseline.bound(),
                            "tol": round(r.baseline.tol, 4),
                        }
                        for r in regs
                    ],
                },
            }
        )
    )
    if regs:
        for r in regs:
            print(f"REGRESSION: {r.describe()}", file=sys.stderr)
        print(
            f"FAIL: {len(regs)} metric(s) regressed beyond tolerance "
            f"(vs {n_hist} history records)",
            file=sys.stderr,
        )
        return 1
    print(
        f"ok: {len(baselines)} tracked metrics within tolerance "
        f"(vs {n_hist} history records)",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
