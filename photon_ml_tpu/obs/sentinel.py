"""Bench regression sentinel: machine-checked perf baselines.

The committed BENCH records (``BENCH_r03..r05``) show order-of-magnitude
swings between rounds (GAME CD 1.19 -> 10.1 iters/s), yet nothing
machine-checks that the NEXT change doesn't silently give those wins
back — the records were write-only history. This module turns them into
a gate:

- :func:`flatten_record` maps one parsed BENCH record (the
  ``{"metric", "value", ..., "extra": {...}}`` JSON line) to flat dotted
  numeric metrics.
- :func:`metric_direction` classifies each metric as higher-is-better
  (throughput, speedup ratios, MFU, AUC), lower-is-better (wall clocks,
  per-device footprints, collective counts), or untracked (environment
  noise: fetch latency, phase walls, registry dumps — regressions there are
  not code regressions).
- :func:`fit_baselines` fits a noise-tolerant baseline per metric over
  the history: median plus a tolerance band widened by the metric's own
  historical dispersion (MAD-scaled), so a metric that legitimately
  swings across rounds gets a wide band instead of a false alarm, while
  a historically-stable metric is held tight.
- :func:`check_record` compares a current record against the baselines
  and returns the regressions (direction-aware). New metrics and missing
  metrics are tolerated — growth must not be penalized.

``benchmarks/regression_sentinel.py`` is the CLI (standalone / CI);
``bench.py --sentinel`` runs the same check on the record it just
produced.
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform as _platform
import re
import statistics
from typing import Dict, List, Optional, Sequence

__all__ = [
    "flatten_record",
    "metric_direction",
    "metric_floor",
    "host_fingerprint",
    "env_change_note",
    "Baseline",
    "Regression",
    "fit_baselines",
    "check_record",
    "load_bench_record",
    "run_sentinel",
]

# Defaults tuned on the real r01..r05 bench history: every metric of r05
# passed against the r01..r04 baseline, while a uniform 30% degradation
# of r05's tracked throughput/wall metrics is flagged.
DEFAULT_TOLERANCE = 0.25
DEFAULT_MAD_K = 4.0
DEFAULT_MIN_SAMPLES = 2

HIGHER_IS_BETTER = 1
LOWER_IS_BETTER = -1
UNTRACKED = 0

# First match wins; order: untracked overrides, then higher, then lower.
_DIRECTION_RULES = (
    # environment / identity noise, not code performance. host.* is the
    # environment FINGERPRINT (host_fingerprint below): identity, never
    # a metric — but run_sentinel uses it to annotate regressions that
    # coincide with an environment change vs the history
    (re.compile(r"(^|\.)host\."), UNTRACKED),
    (re.compile(r"(^|\.)rtt_ms"), UNTRACKED),
    (re.compile(r"dense_wall_incl_rtt_s$"), UNTRACKED),
    (re.compile(r"max_dw"), UNTRACKED),
    (re.compile(r"transfer_gb$"), UNTRACKED),
    (re.compile(r"(^|\.)phase_s\."), UNTRACKED),
    (re.compile(r"(^|\.)metrics\."), UNTRACKED),
    (re.compile(r"(^|\.)cost_book\."), UNTRACKED),
    (re.compile(r"predicted_over_observed$"), UNTRACKED),
    # bigger is better
    (
        re.compile(
            r"(vs_baseline|vs_cpu|vs_sklearn|vs_python_codec|vs_ell"
            r"|speedup)$"
        ),
        HIGHER_IS_BETTER,
    ),
    (re.compile(r"scaling_efficiency$"), HIGHER_IS_BETTER),
    # overlap-scaled collectives (docs/PARALLEL.md, bench_overlap): the
    # share of a sharded objective pass's wall spent on (or exposed by)
    # the feature-space reduction — the DIRECT overlap gate
    # (scaling_efficiency only infers it). Lower = more of the
    # collective hidden under compute / less partition overhead.
    (re.compile(r"collective_wall_frac"), LOWER_IS_BETTER),
    (re.compile(r"\.wall_frac$"), LOWER_IS_BETTER),
    (re.compile(r"(iters_per_s|rec_per_s|per_s)$"), HIGHER_IS_BETTER),
    # ingest pipeline (docs/INGEST.md): host->device bandwidth and the
    # counted-stage overlap fraction rise as the feed improves; the
    # epoch stall fraction (consumer time NOT covered by device math)
    # falls. These gate the decode/transfer/solve overlap directly —
    # wall clocks on a timeshared bench host cannot. The _gbps rule also
    # tracks ckpt_shard_write_gbps (bench_multihost_resilience): the
    # per-process sharded checkpoint write path must not slow down.
    (re.compile(r"_gbps$"), HIGHER_IS_BETTER),
    # elastic multi-host resilience (docs/MULTIHOST.md): the wall from a
    # stalled collective to a clean retried exchange (watchdog deadline
    # + backoff + redo) — explicit rather than via the generic _s rule
    # so the recovery contract stays gated even if the generic ever
    # narrows
    (re.compile(r"recovery_s$"), LOWER_IS_BETTER),
    (re.compile(r"overlap_frac$"), HIGHER_IS_BETTER),
    (re.compile(r"stall_frac$"), LOWER_IS_BETTER),
    # chaos-hardened serving (docs/ROBUSTNESS.md, bench_overload): the
    # fraction of a FIXED offered overload turned away (expired + shed +
    # rejected) falls as the serving path gets faster/smarter; the
    # companion p99_under_overload_ms / breaker_recovery_s gate through
    # the generic _ms/_s lower-is-better rules below
    (re.compile(r"shed_frac$"), LOWER_IS_BETTER),
    # entity-sharded serving + tiered entity cache (docs/SERVING.md,
    # bench_serving_sharded): sustained throughput of the sharded and
    # cache-tier hit paths, the cache hit fraction under the Zipf load
    # the tier exists for, and the per-process resident RE-table
    # footprint (the ~P x drop mesh partitioning buys — creep here is
    # the capacity regression wall clocks cannot see)
    (re.compile(r"_qps$"), HIGHER_IS_BETTER),
    (re.compile(r"hit_frac$"), HIGHER_IS_BETTER),
    (re.compile(r"resident.*bytes"), LOWER_IS_BETTER),
    # model-quality observability (docs/OBSERVABILITY.md "Quality &
    # drift", bench_quality): the serving path's wall with the
    # DriftMonitor sampling vs without (creep here is the quality
    # layer's tax growing), and how many offered requests / how much
    # wall a real covariate shift needs before drift.alarm fires — the
    # retrain-loop trigger must not get slower to notice. The
    # companion sketch_rows_per_s gates through the generic per_s rule.
    (re.compile(r"overhead_ratio$"), LOWER_IS_BETTER),
    (re.compile(r"drift_alarm_latency"), LOWER_IS_BETTER),
    # self-healing loop (docs/LIFECYCLE.md): alarm-to-reload wall for a
    # full retrain cycle — the mean-time-to-recover of the serving
    # fleet after a confirmed drift; auc_recovered gates through the
    # generic auc rule below
    (re.compile(r"retrain_cycle_s$"), LOWER_IS_BETTER),
    # serving fabric (docs/FRONTEND.md, bench_frontend): wall from a
    # whole-replica loss to the router's first successful failover —
    # the fleet's blast-radius clock; explicit (like recovery_s) so
    # the failover contract stays gated independent of the generic
    # _s rule
    (re.compile(r"failover_s$"), LOWER_IS_BETTER),
    # photon-lint self-hosting gate (docs/ANALYSIS.md): total findings
    # over the tree — NEW findings already fail the lint itself, so
    # what this tracks is ratchet debt (baselined + suppressed) creep;
    # the companion lint_wall_s gates through the generic _s rule
    (re.compile(r"lint_findings_total$"), LOWER_IS_BETTER),
    (re.compile(r"(^|\.)mfu$"), HIGHER_IS_BETTER),
    (re.compile(r"hbm_util$"), HIGHER_IS_BETTER),
    (re.compile(r"achieved_tflops$"), HIGHER_IS_BETTER),
    (re.compile(r"auc"), HIGHER_IS_BETTER),
    # smaller is better
    # convergence health (bench_game's decoded fleet summaries): more
    # iterations to converge or a larger non-converged fraction is a
    # solver-quality regression even when wall clocks hold steady
    (re.compile(r"(^|\.)convergence\.median_iters$"), LOWER_IS_BETTER),
    (
        re.compile(r"(^|\.)convergence\.nonconverged_frac$"),
        LOWER_IS_BETTER,
    ),
    # dispatch economy (ROADMAP item 1, device-resident loops): host
    # round trips per training unit — a creeping dispatch count is the
    # latency regression wall clocks on a timeshared bench host cannot
    # see, so it gates directly and platform-invariantly
    (
        re.compile(r"(^|\.)dispatches_per_(path|run|solve)$"),
        LOWER_IS_BETTER,
    ),
    (re.compile(r"(^|\.)game_dispatches_per_run$"), LOWER_IS_BETTER),
    (re.compile(r"(^|\.)dispatches$"), LOWER_IS_BETTER),
    (re.compile(r"(_s|_ms|_mb|_kb|_m)$"), LOWER_IS_BETTER),
    (re.compile(r"(^|\.)passes$"), LOWER_IS_BETTER),
    (re.compile(r"^value$"), LOWER_IS_BETTER),
    (re.compile(r"collectives\."), LOWER_IS_BETTER),
    (re.compile(r"bytes"), LOWER_IS_BETTER),
)


def metric_direction(name: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 untracked."""
    for pattern, direction in _DIRECTION_RULES:
        if pattern.search(name):
            return direction
    return UNTRACKED


# Absolute floors: metrics whose minimum acceptable value is known a
# priori, gated on the CURRENT record alone — no history needed, so the
# gate binds from the very first record that carries the metric (the
# MAD band needs >= min_samples history records first). The multi-device
# scaling efficiency wall_1dev/(N*wall_Ndev) has an honest ceiling of
# ~1/N on the timeshared-CPU bench host (virtual devices share one
# core, wall cannot drop). Through the r06 run the floor was the
# bind-with-zero-history 0.25/N rule — a quarter of the ceiling, i.e.
# "the 2-device regression is back" alarm. With the overlap-scaled path
# landed (PHOTON_COLLECTIVE_MODE=overlap: row-balanced blocking +
# chunked reduce-scatter pipeline, docs/PARALLEL.md) the floors are
# ABSOLUTE per-width targets ~2x higher, set from the measured r07 tree
# (0.32-0.38 / 0.15-0.17 / 0.07-0.09 across bench-box load levels) with
# ~25% headroom for the box's timeshare noise; on ICI hardware (where
# the async collectives actually overlap compute) widths should clear
# these with a wide margin, and the floors should be raised again from
# pod measurements.
_SCALING_FLOORS = {2: 0.25, 4: 0.12, 8: 0.055}
_FLOOR_RULES = (
    (
        re.compile(r"sparse_fs_scaling\.(\d+)\.scaling_efficiency$"),
        lambda m: _SCALING_FLOORS.get(
            int(m.group(1)), 0.25 / int(m.group(1))
        ),
    ),
)


def metric_floor(name: str) -> Optional[float]:
    """The absolute floor for ``name``, or None when only the relative
    history band applies."""
    for pattern, fn in _FLOOR_RULES:
        m = pattern.search(name)
        if m:
            return fn(m)
    return None


def host_fingerprint() -> Dict[str, object]:
    """The environment identity stamped into every BENCH record's
    ``extra.host``: enough to tell "the code regressed" apart from "the
    bench box changed under us". Never initializes a jax backend — the
    version string is importable without one."""
    try:
        import jax

        jax_version = jax.__version__
    except Exception:  # noqa: BLE001 — fingerprints must never fail
        jax_version = None
    return {
        "cpu_count": os.cpu_count(),
        "platform": _platform.platform(),
        "machine": _platform.machine(),
        "python": _platform.python_version(),
        "jax": jax_version,
    }


def env_change_note(history: Sequence[dict], current: dict) -> str:
    """Human-readable diff of ``current``'s host fingerprint vs the most
    recent history record that carries one; ``""`` when nothing changed
    or no fingerprinted history exists. ``history``/``current`` are RAW
    parsed BENCH records (not flattened — flattening drops the strings
    the fingerprint mostly consists of)."""
    cur_fp = (current.get("extra") or {}).get("host")
    if not isinstance(cur_fp, dict):
        return ""
    prev_fp = None
    for rec in reversed(list(history)):
        fp = (rec.get("extra") or {}).get("host")
        if isinstance(fp, dict):
            prev_fp = fp
            break
    if prev_fp is None:
        return ""
    changes = []
    for key in sorted(set(prev_fp) | set(cur_fp)):
        if prev_fp.get(key) != cur_fp.get(key):
            changes.append(
                f"{key} {prev_fp.get(key)!r}->{cur_fp.get(key)!r}"
            )
    return ", ".join(changes)


def flatten_record(parsed: dict) -> Dict[str, float]:
    """Parsed BENCH record -> flat ``{dotted.metric: float}``. ``value``
    keeps its name; ``extra`` flattens recursively; non-numeric leaves
    (metric name, unit, strings) are dropped. Booleans are excluded —
    ``True`` is not a measurement."""
    out: Dict[str, float] = {}

    def walk(prefix: str, obj) -> None:
        if isinstance(obj, dict):
            for k, v in obj.items():
                walk(f"{prefix}.{k}" if prefix else str(k), v)
        elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
            out[prefix] = float(obj)

    if "value" in parsed:
        walk("value", parsed["value"])
    walk("extra", parsed.get("extra") or {})
    return out


@dataclasses.dataclass(frozen=True)
class Baseline:
    """Per-metric fitted baseline: history median plus a relative
    tolerance band (``tol``), direction-aware."""

    metric: str
    median: float
    tol: float
    direction: int
    n_samples: int

    def bound(self) -> float:
        """The worst still-acceptable value."""
        if self.direction == HIGHER_IS_BETTER:
            return self.median * (1.0 - self.tol)
        return self.median * (1.0 + self.tol)


@dataclasses.dataclass(frozen=True)
class Regression:
    metric: str
    current: float
    baseline: Baseline
    # non-empty when the record's host fingerprint differs from the
    # history's — the regression may be the box, not the code
    env_note: str = ""

    def describe(self) -> str:
        arrow = (
            "below" if self.baseline.direction == HIGHER_IS_BETTER else "above"
        )
        out = (
            f"{self.metric}: {self.current:g} is {arrow} the tolerated "
            f"bound {self.baseline.bound():g} (median {self.baseline.median:g}"
            f" over {self.baseline.n_samples} records, band "
            f"±{self.baseline.tol:.0%})"
        )
        if self.env_note:
            out += f" [environment changed vs history: {self.env_note}]"
        return out


def fit_baselines(
    history: Sequence[Dict[str, float]],
    min_samples: int = DEFAULT_MIN_SAMPLES,
    tolerance: float = DEFAULT_TOLERANCE,
    mad_k: float = DEFAULT_MAD_K,
) -> Dict[str, Baseline]:
    """Fit per-metric baselines over flattened history records.

    The band is ``max(tolerance, mad_k * MAD/|median|)``: the floor
    absorbs run-to-run noise every metric has; the MAD term widens the
    band for metrics whose own history swings (a metric that moved 10x
    between rounds cannot honestly gate a 30% change). Metrics seen in
    fewer than ``min_samples`` records, with a ~zero median, or
    classified untracked get no baseline.
    """
    samples: Dict[str, List[float]] = {}
    for rec in history:
        for name, value in rec.items():
            samples.setdefault(name, []).append(value)
    out: Dict[str, Baseline] = {}
    for name, vals in samples.items():
        direction = metric_direction(name)
        if direction == UNTRACKED or len(vals) < min_samples:
            continue
        med = statistics.median(vals)
        if abs(med) < 1e-12:
            continue  # relative bands are meaningless at zero
        mad = statistics.median(abs(v - med) for v in vals)
        tol = max(tolerance, mad_k * mad / abs(med))
        out[name] = Baseline(
            metric=name,
            median=med,
            tol=tol,
            direction=direction,
            n_samples=len(vals),
        )
    return out


def check_record(
    current: Dict[str, float], baselines: Dict[str, Baseline]
) -> List[Regression]:
    """Regressions of ``current`` vs fitted baselines, worst first.
    Metrics absent from either side are tolerated (renames and new
    instrumentation must not fail the gate). Metrics with an absolute
    floor (:func:`metric_floor`) are additionally gated against it —
    history or not."""
    regs: List[Regression] = []
    flagged = set()
    for name, base in baselines.items():
        cur = current.get(name)
        if cur is None:
            continue
        if base.direction == HIGHER_IS_BETTER:
            bad = cur < base.bound()
        else:
            bad = cur > base.bound()
        if bad:
            regs.append(Regression(metric=name, current=cur, baseline=base))
            flagged.add(name)
    for name, cur in current.items():
        floor = metric_floor(name)
        if floor is None or name in flagged or cur >= floor:
            continue
        regs.append(
            Regression(
                metric=name,
                current=cur,
                baseline=Baseline(
                    metric=name,
                    median=floor,
                    tol=0.0,
                    direction=HIGHER_IS_BETTER,
                    n_samples=0,
                ),
            )
        )
    regs.sort(
        key=lambda r: -(
            abs(r.current - r.baseline.median) / abs(r.baseline.median)
        )
    )
    return regs


def load_bench_record(path: str) -> Optional[dict]:
    """The ``parsed`` record of one BENCH_*.json file (None when the
    round failed or the file predates the parsed field)."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        if doc.get("rc", 0) == 0:
            return doc["parsed"]
        return None
    # a bare record (bench.py's own output) is accepted as-is
    if isinstance(doc, dict) and "extra" in doc:
        return doc
    return None


def run_sentinel(
    history_paths: Sequence[str],
    current: dict,
    min_samples: int = DEFAULT_MIN_SAMPLES,
    tolerance: float = DEFAULT_TOLERANCE,
    mad_k: float = DEFAULT_MAD_K,
):
    """History files + a current parsed record -> (regressions,
    fitted baselines, n_history_records). Regressions carry an
    ``env_note`` when the current host fingerprint (``extra.host``)
    differs from the history's — a flag that may be the box, not the
    code."""
    raw_history = []
    for p in history_paths:
        rec = load_bench_record(p)
        if rec is not None:
            raw_history.append(rec)
    baselines = fit_baselines(
        [flatten_record(r) for r in raw_history],
        min_samples=min_samples,
        tolerance=tolerance,
        mad_k=mad_k,
    )
    regs = check_record(flatten_record(current), baselines)
    note = env_change_note(raw_history, current)
    if note:
        regs = [dataclasses.replace(r, env_note=note) for r in regs]
    return regs, baselines, len(raw_history)
