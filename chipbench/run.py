"""One run of one cell: ``python3 -m chipbench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.  The last line of standard output is the result.

Everything that belongs to one configuration, traffic mix, task or per-layer
metric is a file of that name (see README.md); this file only strings them
together: find the chip, set up, open the window, read the memory peak, free
the program, compare with the reference, print.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python can see it

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """No accelerator this cell can be measured on."""


class Run:
    """What one run knows: its cell, and what the window left behind for the
    per-layer readers (counts, host spans, the reduced trace)."""

    def __init__(self, bench, cell, config, traffic, seed, seconds, trace,
                 rehearse):
        self.bench, self.cell = bench, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.tracing, self.rehearse = bool(trace), bool(rehearse)
        self.peaks = None  # this device's row of peaks.json
        self.counts = {}  # name -> number, filled by the task
        self.spans = []  # (name, start_s, end_s) on the host clock
        self.trace = None  # reduce_trace.reduce(...) of the traced window
        self.end_to_end = {}  # name -> value
        self.phases = {}  # phase -> seconds
        self.attempted = 0
        self.failed = 0
        self.compared = []  # (name, value, limit), filled by task.check
        self.fault = ""  # a fault planted under the timed path (tests)
        self._annotate = None

    def param(self, key):
        """A value of the configuration, or its ``rehearse`` stand-in (rows,
        entities and the AUC their model reaches; never a width)."""
        if self.rehearse and key in self.config.get("rehearse", {}):
            return self.config["rehearse"][key]
        return self.config[key]

    def size(self, key):
        return int(self.param(key))

    @contextlib.contextmanager
    def span(self, name):
        """A host span; in a traced run it is also written into the
        profiler's trace, on the device events' clock."""
        ann = self._annotate("chipbench." + name) if self._annotate else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def span_names(self):
        return sorted({name for name, _, _ in self.spans})

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        yield
        self.phases[name] = self.phases.get(name, 0.0) + (
            time.perf_counter() - t0
        )


def _load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(workload):
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load_json(os.path.join(ROOT, entry["file"]))
    traffic = _load_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    )
    return bench, cell, config, traffic


def find_device(cell, rehearse):
    """(device record, peaks row).  Off the chip only a rehearsal goes on."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    record = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }
    table = _load_json(os.path.join(HERE, "peaks.json"))
    if rehearse:
        return record, table["TPU v5 lite"]  # for arithmetic only
    if dev.platform != "tpu":
        raise NoChip(f"platform is {dev.platform!r}, not 'tpu'")
    if dev.device_kind not in table:
        raise NoChip(f"device kind {dev.device_kind!r} is not in peaks.json")
    if len(devices) < int(cell["chips"]):
        raise NoChip(f"{len(devices)} chips, the cell needs {cell['chips']}")
    return record, table[dev.device_kind]


def device_memory():
    """(peak bytes, limit bytes, the runtime's own stats) of the fullest chip.
    The peak is the live buffers' peak plus what the runtime holds reserved
    for the loaded programs' scratch: on the TPU ``peak_bytes_in_use`` leaves
    that scratch out (``peak_bytes_reserved`` carries it), yet no one else
    can use it."""
    import jax

    best = (0, 0, {})
    for d in jax.local_devices():
        stats = {k: int(v) for k, v in (d.memory_stats() or {}).items()}
        peak = stats.get("peak_bytes_in_use", 0) + stats.get(
            "peak_bytes_reserved", 0)
        if peak >= best[0]:
            best = (peak, stats.get("bytes_limit", 0), stats)
    return best


def wanted_metrics(bench, group, cell_name, reported_e2e):
    """The metrics of ``group`` this cell has to (or may) report."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        if cells is None and group == "per_layer" and (
            m["moves"] != "setup_s" and m["moves"] not in reported_e2e
        ):
            continue
        out.append(m)
    return out


def read_layer_metric(name, run):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="CPU, rows and entities shrunk (never widths); no device metric",
    )
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates: one set-up, 8 s at "
                         "each, a line for each on stderr, no result")
    ap.add_argument("--control", action="store_true",
                    help="also read the control: the reference one precision "
                         "lower, in the program's place (stderr, `control`)")
    ap.add_argument("--fault", default="",
                    choices=("", "state_unchanged", "half_batch",
                             "answer_altered"),
                    help="break the timed path underneath (tests, readings)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the traced run's .xplane.pb in place")
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload)
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache")
    )
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        device, peaks = find_device(cell, args.rehearse)
    except NoChip as e:
        print(f"chipbench: no chip to measure on: {e}", file=sys.stderr)
        return 3

    run = Run(bench, cell, config, traffic, args.seed, args.seconds,
              args.trace, args.rehearse)
    run.peaks = peaks
    run.fault = args.fault
    task = importlib.import_module("chipbench.tasks." + config["task"])

    from photon_ml_tpu.obs import compile_events

    compile_events.install_compile_listener()
    state = task.setup(run)
    run.counts["cache_hits_setup"] = compile_events.xla_cache_hits()
    run.counts["compiles_setup"] = compile_events.xla_compile_events()
    gc.collect()
    gc.freeze()
    gc.disable()
    setup_s = time.perf_counter() - _T0

    if args.sweep:
        task.sweep(state, run, [float(x) for x in args.sweep.split(",")])
        task.release(state)
        return 0

    trace_dir = os.path.join(ROOT, ".chipbench_trace", cell["name"])
    if run.tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        run._annotate = jax.profiler.TraceAnnotation
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # it slows every Python call
        with jax.profiler.trace(trace_dir, profiler_options=options):
            task.window(state, run)
        run._annotate = None
        task.count(state, run)
    else:
        task.window(state, run)
    gc.enable()
    run.counts.setdefault(
        "compiles_in_window",
        compile_events.xla_compile_events() - run.counts["compiles_setup"],
    )

    peak, limit, memory_stats = device_memory()
    device["memory_peak_bytes"] = peak
    run.counts.update(memory_peak_bytes=peak, memory_limit_bytes=limit)

    task.release(state)
    t0 = time.perf_counter()
    task.check(state, run)
    run.phases["reference_check_after_window"] = time.perf_counter() - t0
    control = task.control(state, run) if args.control else None
    correct = all(_within(v, lim) for _, v, lim in run.compared) and bool(
        run.compared
    )

    breakdown = None
    if run.tracing:
        from chipbench import reduce_trace

        run.trace = reduce_trace.reduce(trace_dir, run.span_names())
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if run.trace is not None and not args.rehearse:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            breakdown = {
                "device_ops": run.trace["device_ops"][:10],
                "idle_gaps": run.trace["idle_gaps"][:10],
            }

    metrics = {}
    if run.tracing:
        for m in wanted_metrics(bench, "per_layer", cell["name"],
                                run.end_to_end):
            value = read_layer_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        run.end_to_end["setup_s"] = setup_s
        for m in wanted_metrics(bench, "end_to_end", cell["name"], None):
            if m["name"] in run.end_to_end:
                metrics[m["name"]] = {
                    "value": float(run.end_to_end[m["name"]]),
                    "unit": m["unit"],
                }
    if args.rehearse:
        # a CPU run gives counts and correctness, never a device number
        counted = {
            m["name"] for m in bench["per_layer"]
            if m["source"] == "program_counter"
        }
        metrics = {k: v for k, v in metrics.items() if k in counted}

    compared = {
        name: {"value": float(v), "limit": float(lim)}
        for name, v, lim in run.compared
    }
    result = {
        "correct": bool(correct),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
        "phases_s": run.phases,
        "memory_stats": memory_stats,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control is not None:
        for name, v in control.items():
            print(f"control {name}: {v!r}", file=sys.stderr)
        result["control"] = control
    result["compared"] = compared
    for name, c in compared.items():  # the last lines of standard error
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _within(value, limit):
    """A compared number passes at or under its limit; a limit below zero is
    a floor on the negated number (``-auc <= -target``)."""
    return value == value and value <= limit

if __name__ == "__main__":
    sys.exit(main())
