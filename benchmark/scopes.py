"""Device seconds of the measured window by engine phase.

The engines name their phases with ``jax.named_scope``: ``pull.gather``,
``pull.reduce``, ``pull.apply``; ``push.plan``, ``push.dense``,
``push.sparse``, ``push.requeue``.  On a TPU the profiler's ``XLA Ops``
events carry an op's HLO text (``%fusion.16 = f32[...] fusion(...)``)
but no op name, so the reduction joins two inputs: the trace, whose
``XLA Modules`` line says which module each op ran in, and the modules'
optimized HLO as XLA dumps it (``--xla_dump_to``), whose
``metadata={op_name="..."}`` names each op's scope.  An op counts under
the innermost engine scope of its op name; one the compiler left
unnamed, under the scope ``hlo_scopes`` works out from what it fuses
and reads; else under ``other``.  Loops and branches are left out as in
``trace.summarize``.

Idle gaps are labelled as ``trace.summarize`` labels them, with the
program's own spans (``obs.span``, on the profiler's host plane) tried
before the benchmark's job spans.

    python3 benchmark/scopes.py --workload <cell> --seed <n> --seconds <s>

runs the cell once as ``benchmark/run.py --trace 1`` does, with XLA's
dump on and JAX's persistent compilation cache off (a program read from
the cache is not dumped), then prints one more line:
``{"scopes": {scope: device s}, "gaps": [...], "program": {counter:
[calls, total]}}``, the counters taken over the window alone.
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import tempfile

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import trace  # noqa: E402

#: one engine scope among the components of an op name, bare or
#: wrapped by a transform (``vmap(pull.gather)``)
SCOPE = re.compile(r"(?:^|[/(])((?:pull|push)\.[a-z_]+)(?=[)/]|$)")
#: a computation's first line, and an instruction line, of HLO text
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+) .*\{\s*$")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = (.*)$")
OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
NAME = re.compile(r"%[\w.\-]+")
CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
PARAMETER = re.compile(r"\bparameter\((\d+)\)")
OTHER = "other"
#: the engines' phases in the order their ops run within a round
PHASES = ("push.plan", "push.dense", "push.sparse", "push.requeue",
          "pull.gather", "pull.reduce", "pull.apply")
#: the program's spans that label idle gaps, tried before the
#: benchmark's job spans (which enclose them)
PROGRAM_SPANS = ("push.init", "push.dispatch", "pull.dispatch")


def scope_of(op_name: str) -> str:
    """The innermost engine scope in ``op_name``, else 'other'."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else OTHER


def hlo_scopes(text: str) -> dict:
    """{instruction name: scope} of one module's HLO text.

    The TPU compiler drops the op name of some ops it rewrites (a
    scatter becomes an unnamed fusion), so an op with no engine scope of
    its own takes, in turn: a fusion, the scope of its fused root; a
    fused parameter, that of the fusion's operand it stands for; any op,
    the latest phase (``PHASES`` order) among the ops it reads, as no op
    runs before the phase of its inputs; else 'other'."""
    roots, own, reads, calls, param = {}, {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        head = COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = INSTR.match(line)
        if m is None:
            continue
        name, rhs = m.group(1), m.group(2)
        if line.lstrip().startswith("ROOT"):
            roots[comp] = name
        op = OP_NAME.search(rhs)
        own[name] = scope_of(op.group(1)) if op else OTHER
        reads[name] = NAME.findall(rhs.split("metadata=", 1)[0])
        fused = CALLS.search(rhs)
        if fused:
            calls[name] = fused.group(1)
        k = PARAMETER.search(rhs)
        if k:
            param[name] = (comp, int(k.group(1)))
    # a fused computation's parameter k stands for operand k of its fusion
    fusion_of = {c: f for f, c in calls.items()}
    memo: dict = {}

    def resolve(name):
        if name not in memo:
            memo[name] = OTHER  # a cycle reads as unscoped
            memo[name] = _resolve(name)
        return memo[name]

    def _resolve(name):
        if own.get(name, OTHER) != OTHER:
            return own[name]
        if name in calls and calls[name] in roots:
            return resolve(roots[calls[name]])
        if name in param:
            comp, k = param[name]
            ops = [r for r in reads.get(fusion_of.get(comp), ()) if r in own]
            return resolve(ops[k]) if k < len(ops) else OTHER
        phases = [x for x in (resolve(r) for r in reads[name] if r in own)
                  if x in PHASES]
        return max(phases, key=PHASES.index) if phases else OTHER

    return {name: resolve(name) for name in own}


def module_scopes(dump_dir: str) -> dict:
    """{module name: {instruction: scope}} from XLA's optimized-HLO text
    dumps under ``dump_dir``; modules of one name merge."""
    out: dict = {}
    for path in glob.glob(os.path.join(dump_dir, "*after_optimizations.txt")):
        with open(path) as f:
            text = f.read()
        head = text.split(",", 1)[0].split()
        if len(head) == 2 and head[0] == "HloModule":
            out.setdefault(head[1], {}).update(hlo_scopes(text))
    return out


def _module_name(event_name: str) -> str:
    """``jit_loop(1234)`` -> ``jit_loop``."""
    return event_name.split("(", 1)[0]


def device_scopes(pd, scopes: dict) -> dict:
    """{scope: device seconds inside the window}, each device's leaf ops
    attributed through ``scopes`` ({module: {instruction: scope}}),
    averaged over devices."""
    window, totals, devices = None, {}, 0
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace.WINDOW and window is None:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise ValueError("trace holds no 'window' span")
    lo, hi = window
    for plane in pd.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        devices += 1
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       _module_name(ev.name))
                      for ev in lines.get("XLA Modules", ()))
        for ev in lines.get(trace.OPS_LINE, ()):
            s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
            if e <= s or trace.CONTAINER.search(ev.name):
                continue
            mod = next((m for ms, me, m in mods
                        if ms <= ev.start_ns < me), None)
            op = ev.name.split(" = ", 1)[0].strip()
            scope = scopes.get(mod, {}).get(op, OTHER)
            totals[scope] = totals.get(scope, 0.0) + (e - s)
    return {k: v / max(devices, 1) / 1e9 for k, v in totals.items()}


def label_gaps(pd, top: int = 10) -> list:
    """[(label, seconds)] of the window's longest idle gaps, each named by
    the program span covering most of it, else by the job span covering
    most of it."""
    labels = PROGRAM_SPANS + trace.HOST_SPANS
    host, device_ops = {}, []
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            device_ops.append([
                (ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines if line.name == trace.OPS_LINE
                for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace.WINDOW or ev.name in labels:
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    lo, hi = host[trace.WINDOW][0]

    def label(gap):
        # the job spans enclose the program's: a program span that
        # covers any of the gap names it
        for tier in (PROGRAM_SPANS, trace.HOST_SPANS):
            best, name = 0, None
            for span in tier:
                cover = sum(max(0, min(e, gap[1]) - max(s, gap[0]))
                            for s, e in host.get(span, ()))
                if cover > best:
                    best, name = cover, span
            if name:
                return name
        return "host.other"

    out = []
    for ops in device_ops:
        for g in trace.gaps_between([(s, e) for s, e in ops
                                     if e > lo and s < hi], lo, hi):
            out.append((label(g), (g[1] - g[0]) / 1e9))
    return sorted(out, key=lambda x: -x[1])[:top]


def main(argv=None) -> int:
    dump = tempfile.mkdtemp(prefix="xla_dump_")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump}",
        "--xla_dump_hlo_as_text")))
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import jax

    from benchmark import harness
    from lux_tpu import obs

    seen = {}
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    summarize = harness.trace.summarize

    def start_trace(*a, **k):
        seen["before"] = obs.counters()
        return start(*a, **k)

    def stop_trace(*a, **k):
        seen["after"] = obs.counters()
        return stop(*a, **k)

    def summarize_scopes(pd, *a, **k):
        seen["scopes"] = device_scopes(pd, module_scopes(dump))
        seen["gaps"] = label_gaps(pd)
        return summarize(pd, *a, **k)

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace
    harness.trace.summarize = summarize_scopes
    argv = list(sys.argv[1:] if argv is None else argv)
    try:
        rc = harness.main(argv + ["--trace", "1"])
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        harness.trace.summarize = summarize
        shutil.rmtree(dump, ignore_errors=True)
    before = seen.get("before", {})
    program = {}
    for k, (c, t) in seen.get("after", {}).items():
        c0, t0 = before.get(k, (0, 0))
        if c > c0:
            program[k] = [c - c0, t - t0]
    print(json.dumps({"scopes": seen.get("scopes"), "gaps": seen.get("gaps"),
                      "program": program}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
