"""One run of one cell: data set, set-up, measured window, comparison.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs the cell named in ``BENCHMARK.json``.  Everything
that belongs to one cell is found by name:

- ``configs/<config>.json``: the data set, the app and its semantics,
  and the limits of the numbers compared;
- ``traffic/<traffic>.json``: the mix, read by ``traffic.py``;
- ``apps/<app>.py``: set-up, one job, the useful work of a job, and the
  comparison with the benchmark's own reference;
- ``metrics/<metric>.py`` (end-to-end) and ``layers/<metric>.py``
  (per-layer): one reader each, ``read(run) -> float | None``.

Phases, in order: the data set (generated from the seed, or read back
from ``.cache/``; printed on its own line and kept out of ``setup_s``),
set-up (the program's load, shard build, placement and compile, and one
warm-up job), the window (jobs back to back until ``--seconds`` have
passed; the last job runs to its end), then the peak device memory, and
last the comparison of the window's answers with the reference.

The last line of standard output is the result; the numbers compared,
each with its limit, are the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run exits 3 and
prints no result.  ``--cpu-rehearsal <scale>`` runs the same path on the
CPU at a small Kronecker scale: its numbers are no device numbers, so
its result line carries them under ``rehearsal`` and no ``metrics``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time
import types

from benchmark import graph500, trace, traffic

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
#: JAX's own compile-phase events: lowering to MLIR, and the backend
#: compile (which includes reading the persistent compile cache).  The
#: jaxpr trace event is left out: eager op-by-op dispatch records one
#: per operation, and every jit (re)trace is followed by a lowering.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoDevice(Exception):
    """No TPU, or fewer chips than the cell asks for."""


class Spans:
    """Host-clock spans, also written into the profiler's trace."""

    def __init__(self):
        self.total: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.total[name] = self.total.get(name, 0.0) + (
            time.perf_counter() - t)


class CompileClock:
    """Seconds of JAX's compile events, by phase of the run."""

    def __init__(self):
        self.phase = "setup"
        self.total = {"setup": 0.0, "window": 0.0, "after": 0.0}

    def __call__(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.total[self.phase] += duration


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the cell's control in the program's place "
                         "(for setting limits; never a measurement)")
    ap.add_argument("--cpu-rehearsal", type=int, metavar="SCALE",
                    help="run on the CPU at this Kronecker scale")
    ap.add_argument("--readings", metavar="SEED,SEED,...",
                    help="for setting limits: run each seed, then its "
                         "control, in this one process, and print only "
                         "the numbers compared")
    return ap.parse_args(argv)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def _metrics_of(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def _refuse_tuning():
    """A cell measures the program's own defaults."""
    if os.path.exists(os.path.join(ROOT, ".lux_winners.json")):
        raise SystemExit(".lux_winners.json exists: it changes the "
                         "program's defaults; remove it to measure")
    tuned = sorted(k for k in os.environ if k.startswith("LUX_"))
    if tuned:
        raise SystemExit(f"LUX_* settings change the program: {tuned}")


def _devices(chips: int, rehearsal: bool):
    import jax

    devs = jax.devices()
    if not rehearsal:
        if devs[0].platform != "tpu":
            raise NoDevice(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < chips:
            raise NoDevice(f"{len(devs)} chip(s), the cell needs {chips}")
    return devs[:chips]


def _peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _line(**kw):
    print(json.dumps(kw), flush=True)


def measure(args, t0: float, warm_up: bool = True) -> dict:
    """The run; returns the result object (the last line).  Only the
    readings, in a process whose programs are already compiled, leave
    the warm-up job out."""
    _refuse_tuning()
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = _cell(bench, args.workload)
    conf = _json(os.path.join(BENCH_DIR, "configs", f"{cell['config']}.json"))
    mix = traffic.load(os.path.join(BENCH_DIR, "traffic"), cell["traffic"])
    app = importlib.import_module(f"benchmark.apps.{conf['app']}")
    rehearsal = args.cpu_rehearsal is not None

    import jax

    devs = _devices(cell["chips"], rehearsal)
    from lux_tpu.utils import compile_cache

    compile_cache.enable()

    # the data set: the dataset's cost, not the system's
    d0 = time.perf_counter()
    params = dict(conf["dataset"])
    if rehearsal:
        params["scale"] = args.cpu_rehearsal
    name = "graph500-s{scale}-ef{edge_factor}-undirected".format(**params)
    if params["generator"] != "graph500 kronecker":
        name += "-" + params["generator"].replace(" ", "-")
    graph, path, made = graph500.dataset(CACHE_DIR, name, params, args.seed)
    prep = app.prepare(conf, graph)
    sources = traffic.draw(mix, prep["eligible"])
    works = {src: app.work(conf, graph, prep, src) for src in sources}
    dataset_s = time.perf_counter() - d0
    _line(phase="dataset", name=name, seed=args.seed, nv=graph.nv,
          ne=graph.ne, generated=made, seconds=dataset_s,
          maxrss_gb=graph500.maxrss_gb())

    spans, clock = Spans(), CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        st = app.setup(conf, path, spans,
                       control=graph if args.control else None)
        w0 = time.perf_counter()
        if warm_up:
            app.job(st, sources[0], spans)
        warm_s = time.perf_counter() - w0
        setup_s = time.perf_counter() - t0 - dataset_s
        _line(phase="setup", setup_s=setup_s, warmup_job_s=warm_s,
              graph_load_s=spans.total.get("graph.load"),
              compile_s=clock.total["setup"])

        tmp = tempfile.TemporaryDirectory() if args.trace else None
        if tmp:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tmp.name, profiler_options=opts)
        clock.phase = "window"
        done, edges, nbytes = [], 0, 0
        setup_spans = dict(spans.total)
        spans.total.clear()
        i = 0
        with jax.profiler.TraceAnnotation("window"):
            start = time.perf_counter()
            while True:
                src = sources[i % len(sources)]
                i += 1
                ans = app.job(st, src, spans)
                end = time.perf_counter()
                edges += works[src][0]
                nbytes += works[src][1]
                done.append((src, ans))
                if end - start >= args.seconds:
                    break
        clock.phase = "after"
        summary = None
        if tmp:
            jax.profiler.stop_trace()
            if not rehearsal:
                summary = trace.summarize(trace.load(tmp.name))
            tmp.cleanup()
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    window_s = end - start
    peak = _peak_bytes(devs)
    _line(phase="window", jobs=len(done), seconds=window_s,
          useful_edges=edges, useful_bytes=nbytes, job_spans=spans.total,
          compile_s=clock.total["window"], memory_peak_bytes=peak)
    del st
    gc.collect()

    # the comparison, once the program's state is freed
    numbers, failed = {}, 0
    c0 = time.perf_counter()
    for src, ans in done:
        got = app.check(conf, graph, prep, src, ans)
        failed += any(not got[k] <= conf["limits"][k] for k in got)
        for k, v in got.items():
            numbers[k] = max(numbers.get(k, v), v)
    _line(phase="compare", jobs=len(done), seconds=time.perf_counter() - c0)
    checks = {k: {"value": v, "limit": conf["limits"][k]}
              for k, v in numbers.items()}
    # a NaN is not <= its limit, so it counts as failed
    correct = bool(checks) and failed == 0

    # what the metric readers read
    run = types.SimpleNamespace(
        app=conf["app"], setup_s=setup_s, window_s=window_s, edges=edges,
        useful_bytes=nbytes, spans=setup_spans, compile_s=clock.total,
        summary=summary, device_kind=devs[0].device_kind)
    entries = bench["per_layer"] if args.trace else bench["end_to_end"]
    values = {}
    for m in _metrics_of(entries, args.workload):
        kind = "layers" if args.trace else "metrics"
        v = _module(kind, m["name"]).read(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(done), "failed": failed}
    if rehearsal:
        result["metrics"] = {}
        result["rehearsal"] = {"note": "CPU run: not a device number",
                               "metrics": values}
    else:
        result["metrics"] = values
    result["device"] = device
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in summary.ops],
                               "idle_gaps": [list(x) for x in summary.gaps]}
    result["checks"] = checks
    return result


def readings(args) -> None:
    """The lower and upper readings of each number compared: the
    program's runs and the control's, seed by seed, for each of the
    comma-separated workloads (cells of one data set share its graph)."""
    warmed = set()
    for seed in [int(x) for x in args.readings.split(",")]:
        for cell in args.workload.split(","):
            for control in (False, True):
                ns = argparse.Namespace(**{**vars(args), "seed": seed,
                                           "workload": cell,
                                           "control": control})
                res = measure(ns, time.perf_counter(),
                              warm_up=(cell, control) not in warmed)
                warmed.add((cell, control))
                _line(phase="reading", workload=cell, seed=seed,
                      control=control, correct=res["correct"],
                      jobs=res["attempted"], checks=res["checks"])


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    try:
        if args.readings:
            readings(args)
            return 0
        result = measure(args, t0)
    except NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
