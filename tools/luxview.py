#!/usr/bin/env python
"""luxview — render a luxtrace event log into a human report.

Usage:
    python tools/luxview.py --latest                 # newest run under the root
    python tools/luxview.py <run_id | run dir | events.jsonl> [--out FILE]
    python tools/luxview.py --list                   # runs under the root

The report sections, in order: post-mortem (spans left OPEN by a dead
process — an aborted chip window's first question), the phase waterfall
(every span, nested, with offsets/durations on the shared monotonic
clock), distributed traces (the cross-process waterfalls luxstitch
builds from the fleet's trace-context span attrs, skew-corrected, with
fault injections interleaved), per-iteration telemetry curves (the
on-device rings flushed at run end), the last serving-metrics
snapshot, and the bench rows that carried this run_id.

Pure stdlib and jax-free (the same bare-package stub as luxcheck): a
post-mortem must render on a host whose jax install is in ANY state.  Reading is safe on live logs — unfinished spans simply
show as OPEN.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

import _jaxfree  # noqa: E402
import luxstitch  # noqa: E402  — the stitcher library (jax-free too)

REPO = _jaxfree.REPO
_rec = _jaxfree.load("lux_tpu.obs.recorder")

#: sibling spans of one name under one parent collapse into a single
#: aggregate waterfall row past this count (the plan-build fan-out is
#: hundreds of per-part/per-bucket spans; the report needs one line)
COLLAPSE_AT = 6

SPARK = " ▁▂▃▄▅▆▇█"


def sparkline(values, width: int = 60) -> str:
    """ASCII curve: values bucketed to ``width`` columns (mean per
    bucket), scaled to the 8-level block ramp."""
    vals = [float(v) for v in values]
    if not vals:
        return ""
    if len(vals) > width:
        n = len(vals)
        vals = [
            sum(vals[i * n // width:(i + 1) * n // width])
            / max((i + 1) * n // width - i * n // width, 1)
            for i in range(width)
        ]
    lo, hi = min(vals), max(vals)
    span = hi - lo
    if span <= 0:
        return SPARK[4] * len(vals)
    return "".join(SPARK[1 + int(round((v - lo) / span * 7))] for v in vals)


def load_events(paths):
    """Merge event files: (metas, spans{sid->dict}, points, bad_lines)."""
    metas, points, bad = [], [], 0
    spans = {}
    order = 0
    for path in paths:
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.readlines()
        except OSError:
            continue
        for raw in lines:
            raw = raw.strip()
            if not raw:
                continue
            try:
                ev = json.loads(raw)
            except ValueError:
                bad += 1  # torn final line of a killed process
                continue
            kind = ev.get("e")
            if kind == "m":
                metas.append(ev)
            elif kind == "b":
                spans[ev.get("s")] = {
                    "name": ev.get("n", "?"), "t0": float(ev.get("t", 0.0)),
                    "t1": None, "ok": None, "parent": ev.get("p"),
                    "attrs": ev.get("a", {}), "end_attrs": {},
                    "order": order}
                order += 1
            elif kind == "e":
                sp = spans.get(ev.get("s"))
                if sp is not None:
                    sp["t1"] = float(ev.get("t", 0.0))
                    sp["ok"] = bool(ev.get("ok", True))
                    sp["end_attrs"] = ev.get("a", {})
            elif kind == "p":
                points.append({"name": ev.get("n", "?"),
                               "t": float(ev.get("t", 0.0)),
                               "attrs": ev.get("a", {})})
    return metas, spans, points, bad


def _fmt_attrs(attrs: dict, limit: int = 4) -> str:
    items = list(attrs.items())[:limit]
    if not items:
        return ""
    body = ", ".join(f"{k}={v}" for k, v in items
                     if not isinstance(v, (list, dict)))
    return f"  [{body}]" if body else ""


def _dur(sp, t_end: float) -> float:
    return (sp["t1"] if sp["t1"] is not None else t_end) - sp["t0"]


def render_waterfall(spans: dict, out: list, max_rows: int = 400) -> None:
    if not spans:
        out.append("(no spans recorded)")
        return
    t0 = min(sp["t0"] for sp in spans.values())
    t_end = max([sp["t1"] for sp in spans.values()
                 if sp["t1"] is not None] or [t0])
    t_end = max(t_end, max(sp["t0"] for sp in spans.values()))
    children: dict = {}
    for sid, sp in spans.items():
        parent = sp["parent"] if sp["parent"] in spans else None
        children.setdefault(parent, []).append(sid)
    for sids in children.values():
        sids.sort(key=lambda s: (spans[s]["t0"], spans[s]["order"]))
    rows = [0]

    def emit(sid, depth):
        if rows[0] >= max_rows:
            return
        sp = spans[sid]
        d = _dur(sp, t_end)
        state = ""
        if sp["t1"] is None:
            state = "  ** OPEN **"
        elif sp["ok"] is False:
            state = "  !! failed"
        # end attrs (Span.set / obs_span --rc) merge over begin attrs:
        # a failed step's exit code must be visible in the one report
        out.append(f"  {sp['t0'] - t0:9.3f}s  {'  ' * depth}"
                   f"{sp['name']:<{max(36 - 2 * depth, 8)}} "
                   f"{d:9.3f}s"
                   f"{_fmt_attrs({**sp['attrs'], **sp['end_attrs']})}"
                   f"{state}")
        rows[0] += 1
        emit_group(sid, depth + 1)

    def emit_group(parent, depth):
        by_name: dict = {}
        for sid in children.get(parent, []):
            by_name.setdefault(spans[sid]["name"], []).append(sid)
        collapsed = set()
        for sid in children.get(parent, []):
            name = spans[sid]["name"]
            if name in collapsed:
                continue
            group = by_name[name]
            if len(group) > COLLAPSE_AT:
                # fan-outs (per-part plan builds) render as ONE aggregate
                # row at their first occurrence; everything else stays in
                # plain start-time order
                durs = [_dur(spans[s], t_end) for s in group]
                n_open = sum(1 for s in group if spans[s]["t1"] is None)
                first = spans[group[0]]
                out.append(
                    f"  {first['t0'] - t0:9.3f}s  {'  ' * depth}"
                    f"{name} ×{len(group)}"
                    f"{'':<{max(36 - 2 * depth - len(name) - 5, 1)}}"
                    f" total {sum(durs):9.3f}s  "
                    f"(avg {sum(durs) / len(durs):.3f}s, "
                    f"max {max(durs):.3f}s"
                    + (f", {n_open} OPEN" if n_open else "") + ")")
                rows[0] += 1
                collapsed.add(name)
                continue
            emit(sid, depth)

    emit_group(None, 0)
    if rows[0] >= max_rows:
        out.append(f"  ... (truncated at {max_rows} rows)")


def render_rings(points, out: list) -> None:
    rings = [p for p in points if p["name"] == "telemetry.ring"]
    if not rings:
        out.append("(no on-device telemetry rings in this log)")
        return
    for p in rings:
        a = p["attrs"]
        cols = a.get("cols") or []
        rows = a.get("rows") or []
        n = a.get("n", len(rows))
        extra = {k: v for k, v in a.items()
                 if k not in ("kind", "cols", "rows", "n")}
        out.append(f"### ring: {a.get('kind', '?')} — {n} iteration(s) "
                   f"pushed, {len(rows)} recorded{_fmt_attrs(extra)}")
        if not rows or not cols:
            out.append("")
            continue
        for ci in range(1, len(cols)):
            series = [r[ci] for r in rows if len(r) > ci]
            if not series:
                continue
            out.append(f"  {cols[ci]:>12}: "
                       f"{sparkline(series)}  "
                       f"(first={series[0]:g}, last={series[-1]:g}, "
                       f"max={max(series):g})")
        head = rows[:4]
        tail = rows[-2:] if len(rows) > 6 else rows[4:]
        out.append("  " + "  ".join(f"{c:>12}" for c in cols))
        for r in head:
            out.append("  " + "  ".join(f"{v:12g}" for v in r))
        if len(rows) > 6:
            out.append(f"  {'...':>12}")
        for r in tail:
            out.append("  " + "  ".join(f"{v:12g}" for v in r))
        out.append("")


def render_serve(points, out: list) -> None:
    snaps = [p for p in points if p["name"] == "serve.metrics"]
    if not snaps:
        out.append("(no serving-metrics snapshots in this log)")
        return
    a = snaps[-1]["attrs"]
    lat = a.get("latency_ms") or {}
    wait = a.get("queue_wait_ms") or {}
    out.append(f"snapshots: {len(snaps)} (showing last)")
    out.append(f"  completed={a.get('completed', 0)}  "
               f"timeouts={a.get('timeouts', 0)}  "
               f"rejected={a.get('rejected', 0)}  "
               f"batches={a.get('batches', 0)}")
    if "qps" in a:
        out.append(f"  qps={a['qps']}")
    if lat:
        out.append("  latency_ms: "
                   + "  ".join(f"{k}={v}" for k, v in lat.items()))
    if wait:
        out.append("  queue_wait_ms: "
                   + "  ".join(f"{k}={v}" for k, v in wait.items()))
    for k in ("queue_depth_max", "batch_occupancy", "warm_batch_ratio"):
        if k in a:
            out.append(f"  {k}={a[k]}")


def render_bench(points, out: list) -> None:
    rows = [p for p in points if p["name"] == "bench.row"]
    if not rows:
        out.append("(no bench rows in this log)")
        return
    out.append(f"{'metric':<48} {'value':>12} {'unit':<8} method")
    for p in rows:
        a = p["attrs"]
        out.append(f"{str(a.get('metric', ''))[:48]:<48} "
                   f"{a.get('value', ''):>12} {str(a.get('unit', '')):<8} "
                   f"{a.get('method', '')}")


def render_dtraces(stitched, out: list, max_traces: int = 8) -> None:
    """The cross-process waterfalls (luxstitch): one block per
    distributed trace, largest first."""
    traces = (stitched or {}).get("traces") or {}
    if not traces:
        out.append("(no distributed traces in this log — fleet frames "
                   "record them when LUX_DTRACE is on)")
        return
    offs = {p: round(c, 6)
            for p, c in stitched["offsets"].items() if c}
    out.append(f"{len(traces)} trace(s); clock corrections: "
               f"{offs if offs else 'none (shared clock)'}")
    out.append("")
    ordered = sorted(traces.items(),
                     key=lambda kv: (-len(kv[1]["spans"]),
                                     kv[1]["t0"]))
    for tid, tr in ordered[:max_traces]:
        luxstitch.render_trace(tid, tr, out)
    if len(ordered) > max_traces:
        out.append(f"... ({len(ordered) - max_traces} more; "
                   "tools/luxstitch.py renders them all)")


def render(metas, spans, points, bad, label: str, stitched=None) -> str:
    out = []
    run = metas[0].get("run") if metas else "?"
    out.append(f"# luxtrace report — run {run}")
    out.append("")
    if metas:
        wall0 = min(m.get("wall", 0.0) for m in metas)
        pids = sorted({m.get("pid") for m in metas})
        out.append(f"- started: "
                   f"{time.strftime('%Y-%m-%d %H:%M:%S', time.localtime(wall0))}"
                   f" (wall)")
        out.append(f"- processes: {len(pids)} (pids {pids})")
    n_open = sum(1 for sp in spans.values() if sp["t1"] is None)
    n_fail = sum(1 for sp in spans.values() if sp["ok"] is False)
    out.append(f"- events: {len(spans)} span(s), {len(points)} point(s)"
               + (f", {bad} torn line(s)" if bad else ""))
    out.append(f"- source: {label}")
    out.append("")
    if n_open or n_fail:
        out.append("## Post-mortem")
        out.append("")
        if n_open:
            out.append(f"{n_open} span(s) left OPEN — the process died (or "
                       "is still running) inside:")
            for sp in sorted((s for s in spans.values() if s["t1"] is None),
                             key=lambda s: s["t0"]):
                out.append(f"  - {sp['name']}{_fmt_attrs(sp['attrs'])}")
        if n_fail:
            out.append(f"{n_fail} span(s) exited via an exception:")
            for sp in sorted((s for s in spans.values()
                              if s["ok"] is False), key=lambda s: s["t0"]):
                out.append(f"  - {sp['name']}"
                           f"{_fmt_attrs({**sp['attrs'], **sp['end_attrs']})}")
        out.append("")
    out.append("## Phase waterfall")
    out.append("")
    render_waterfall(spans, out)
    out.append("")
    out.append("## Distributed traces")
    out.append("")
    render_dtraces(stitched, out)
    out.append("")
    out.append("## On-device iteration telemetry")
    out.append("")
    render_rings(points, out)
    out.append("")
    out.append("## Serving metrics")
    out.append("")
    render_serve(points, out)
    out.append("")
    out.append("## Bench rows")
    out.append("")
    render_bench(points, out)
    out.append("")
    out.append(f"run_id: {run}")
    return "\n".join(out) + "\n"


def resolve_target(target, root: str, latest: bool):
    """(event file list, label) for a run id / dir / file / --latest."""
    if latest:
        runs = sorted(glob.glob(os.path.join(root, "*")),
                      key=lambda p: os.path.getmtime(p)
                      if os.path.isdir(p) else 0)
        runs = [r for r in runs if os.path.isdir(r)]
        if not runs:
            return [], root
        target = runs[-1]
    if target is None:
        return [], root
    if os.path.isfile(target):
        return [target], target
    d = target if os.path.isdir(target) else os.path.join(root, target)
    if os.path.isdir(d):
        return sorted(glob.glob(os.path.join(d, "events-*.jsonl"))), d
    return [], target


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render a luxtrace event log (flight-recorder "
                    "post-mortem, waterfall, telemetry, serve)")
    ap.add_argument("target", nargs="?",
                    help="run id, run dir, or events-*.jsonl file")
    ap.add_argument("--latest", action="store_true",
                    help="newest run under the event-log root")
    ap.add_argument("--list", action="store_true",
                    help="list runs under the event-log root")
    ap.add_argument("--root", default=None,
                    help="event-log root (default: LUX_OBS_DIR or the "
                         "uid-scoped tmp dir)")
    ap.add_argument("--out", default=None,
                    help="write the report here instead of stdout")
    args = ap.parse_args(argv)

    root = args.root or _rec.default_root()
    if args.list:
        runs = sorted(glob.glob(os.path.join(root, "*")))
        for r in runs:
            if os.path.isdir(r):
                files = glob.glob(os.path.join(r, "events-*.jsonl"))
                print(f"{os.path.basename(r)}  ({len(files)} file(s))")
        if not runs:
            print(f"(no runs under {root})")
        return 0

    if not args.target and not args.latest:
        ap.print_usage(sys.stderr)
        print("error: give a run id/dir/file, --latest, or --list",
              file=sys.stderr)
        return 2
    files, label = resolve_target(args.target, root, args.latest)
    if not files:
        print(f"luxview: no event files found for "
              f"{args.target or '--latest'} (root {root})", file=sys.stderr)
        return 2
    metas, spans, points, bad = load_events(files)
    stitched = luxstitch.stitch(luxstitch.load_files(files))
    report = render(metas, spans, points, bad, label, stitched=stitched)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(report)
        print(f"luxview: report -> {args.out} "
              f"({len(spans)} spans, {len(points)} points)")
    else:
        sys.stdout.write(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
