"""Reduction of a profiler trace to device busy time, idle gaps and ops.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (no other dependency).  Device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation executed.  Host planes hold the benchmark's own
``jax.profiler.TraceAnnotation`` spans on the same clock: ``window``
brackets the measured window, and the job spans (``job.init``,
``job.run``, ``job.fetch``, ``root.prepare``) say what the host was
doing in each idle gap.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: the host spans that label idle gaps, innermost first
HOST_SPANS = ("root.prepare", "job.init", "job.fetch", "job.run")
WINDOW = "window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: ops that hold other ops (a loop, a branch, a call): their events span
#: their children's, so they count for busy time but not as ops.  A
#: ``custom-call(`` (a Pallas kernel) is an op.
CONTAINER = re.compile(r"(?<![\w-])(while|conditional|call)\(")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices of the union of op intervals
    devices: int
    ops: list  # [(name, seconds)] summed over devices, largest first
    gaps: list  # [(host span, seconds)], longest first


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_between(intervals, lo: float, hi: float):
    """The idle (start, end) stretches of [lo, hi] that no interval
    covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def short_name(op: str) -> str:
    """An op's HLO text cut to its name and result shape:
    ``%fusion.17 = f32[4194304]``."""
    head = op.split("{", 1)[0].split("(", 1)[0]
    return head.strip()[:80]


def _label(gap, spans) -> str:
    """The host span covering most of ``gap``; 'host.other' if none."""
    best, name = 0.0, "host.other"
    for label in HOST_SPANS:
        cover = sum(max(0.0, min(e, gap[1]) - max(s, gap[0]))
                    for s, e in spans.get(label, ()))
        if cover > best:
            best, name = cover, label
    return name


def summarize(pd, top: int = 10) -> Summary:
    """Reduce a ``ProfileData`` to the window's device numbers.  Times
    in the trace are nanoseconds; the summary is in seconds."""
    host, device_ops = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name in HOST_SPANS:
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    if not host.get(WINDOW):
        raise ValueError("trace holds no 'window' span")
    if not device_ops:
        raise ValueError("trace holds no TPU device plane")
    lo, hi = host[WINDOW][0]
    busy, totals, all_gaps = [], {}, []
    for ops in device_ops:
        inside = [(max(s, lo), min(e, hi), n) for n, s, e in ops
                  if e > lo and s < hi]
        ivs = [(s, e) for s, e, _ in inside]
        busy.append(union_length(ivs))
        for s, e, n in inside:
            if not CONTAINER.search(n):
                n = short_name(n)
                totals[n] = totals.get(n, 0.0) + (e - s)
        all_gaps += gaps_between(ivs, lo, hi)
    gaps = sorted(((_label(g, host), (g[1] - g[0]) / 1e9)
                   for g in all_gaps), key=lambda x: -x[1])
    ops = sorted(((n, t / 1e9) for n, t in totals.items()),
                 key=lambda x: -x[1])
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9,
                   devices=len(device_ops), ops=ops[:top], gaps=gaps[:top])


def load(trace_dir: str):
    """The ``ProfileData`` of the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(found[-1])
