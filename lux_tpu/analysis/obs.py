"""Observability-safety checkers (LUX-O*): host syncs and flight-recorder
API misuse in the hot loops.

The luxtrace design contract (docs/OBSERVABILITY.md) is that telemetry is
ALWAYS on because it never touches the hot path: per-iteration counters
ride the compiled loop carry as a static-shape ring (lux_tpu.obs.ring)
and reach the host exactly once, after the loop.  The reference instead
fences every iteration on the host (-verbose, sssp_gpu.cu:513-518) —
that pattern serializes dispatch and is the single cheapest way to ruin
a chip window.  These lints reject it statically:

* LUX-O001 — a host-sync primitive (``block_until_ready`` /
  ``device_get`` / ``copy_to_host_async``) inside a TRACED body (jit /
  shard_map / scan / while_loop / fori_loop / cond / pallas_call).  At
  best a no-op at trace time, at worst an io_callback-shaped stall baked
  into every iteration.
* LUX-O002 — the flight recorder's HOST half (``obs.span`` /
  ``obs.point`` / ``obs.count`` / ``recorder()`` / ``ring_rows`` /
  ``emit_ring``) inside a traced body.  Spans and counts run at trace
  time there — the event log would record compile-time, not run-time,
  and a retrace would duplicate it.  Inside compiled code the legal
  telemetry is ``ring_push`` on a carried ring and ``jax.named_scope``
  (op-name metadata, no runtime op).
* LUX-O003 — per-iteration telemetry fetch: ``ring_rows``/``emit_ring``
  lexically inside a Python loop that also drives a compiled runner
  (``run_pull_fixed``/``run_pull_until``/``run_push``/a compiled
  ``loop(...)``).  The ring contract is ONE fetch at run end; fetching
  per chunk re-introduces the reference's per-iteration fence.
* LUX-O004 — host-callback primitives (``jax.debug.print`` /
  ``jax.debug.callback`` / ``io_callback``) inside a traced body in the
  shipped tree.  Debug-only affordances; each one is a device->host
  round trip per execution.
* LUX-O005 — distributed trace-context API (``obs/dtrace.py``: mint /
  child / child_of / wire_ctx / tspan / emit_span / to_wire /
  from_wire) inside a traced body.  A context is host metadata: minted
  inside a jit body it runs at TRACE time, baking one span id into the
  compiled program — every execution would then "belong" to the trace
  that happened to be live at compile time, which is precisely the
  lie a tracing system must never tell.  Contexts are minted and
  propagated strictly outside compiled code.

Pure stdlib AST like the rest of the suite — the traced-context
detection is shared with the tracing-safety family (tracing.py).
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Set, Tuple

from lux_tpu.analysis.core import Checker, Finding, Module, call_name
from lux_tpu.analysis.tracing import traced_functions

#: dotted call names that force a device->host sync wherever they run
_SYNC_CALLS = {"jax.block_until_ready", "block_until_ready",
               "jax.device_get", "device_get"}
#: method names that sync when called on any array
_SYNC_METHODS = {"block_until_ready", "copy_to_host_async"}

#: host-callback primitives (LUX-O004)
_CALLBACK_CALLS = {"jax.debug.print", "debug.print", "jax.debug.callback",
                   "debug.callback", "io_callback",
                   "jax.experimental.io_callback", "host_callback.call",
                   "jax.experimental.host_callback.call"}

#: recorder-API member names, resolved against the obs-package aliases
_RECORDER_MEMBERS = {"span", "point", "count", "counters", "recorder"}
#: ring HOST-fetch members (ring_push is the traced-side API and legal)
_RING_FETCH_MEMBERS = {"ring_rows", "emit_ring"}

#: distributed trace-context API (LUX-O005): mutating/minting a trace
#: context inside a traced body runs at trace time and bakes one id
#: into the compiled program
_DTRACE_MEMBERS = {"mint", "child", "child_of", "wire_ctx", "tspan",
                   "emit_span", "to_wire", "from_wire", "wire_point"}

#: compiled-runner call names for LUX-O003 (suffix match: methods and
#: module-qualified forms both count)
_RUNNER_SUFFIXES = ("run_pull_fixed", "run_pull_until", "run_push",
                    "run_pull_fixed_overlapped")


def _obs_aliases(mod: Module) -> Tuple[Set[str], Set[str], Set[str],
                                       Set[str], Set[str], Set[str]]:
    """(obs_module_aliases, ring_module_aliases, direct_recorder_names,
    direct_ringfetch_names, dtrace_module_aliases, direct_dtrace_names):
    names this module binds to lux_tpu.obs / lux_tpu.obs.ring /
    lux_tpu.obs.dtrace / individual recorder+ring+dtrace functions.
    Import-resolution keeps the checker precise: a stray local
    ``span()`` helper is not a finding."""
    obs_mods: Set[str] = set()
    ring_mods: Set[str] = set()
    rec_names: Set[str] = set()
    fetch_names: Set[str] = set()
    dtrace_mods: Set[str] = set()
    dtrace_names: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name in ("lux_tpu.obs", "lux_tpu.obs.recorder"):
                    obs_mods.add(a.asname or a.name)
                elif a.name == "lux_tpu.obs.ring":
                    ring_mods.add(a.asname or a.name)
                elif a.name == "lux_tpu.obs.dtrace":
                    dtrace_mods.add(a.asname or a.name)
        elif isinstance(node, ast.ImportFrom):
            m = node.module or ""
            for a in node.names:
                bound = a.asname or a.name
                if m == "lux_tpu" and a.name == "obs":
                    obs_mods.add(bound)
                elif m == "lux_tpu.obs" and a.name == "ring":
                    ring_mods.add(bound)
                elif m == "lux_tpu.obs" and a.name == "dtrace":
                    dtrace_mods.add(bound)
                elif m == "lux_tpu.obs" and a.name == "recorder":
                    obs_mods.add(bound)
                elif m in ("lux_tpu.obs", "lux_tpu.obs.recorder") and (
                        a.name in _RECORDER_MEMBERS):
                    rec_names.add(bound)
                elif m in ("lux_tpu.obs", "lux_tpu.obs.ring") and (
                        a.name in _RING_FETCH_MEMBERS):
                    fetch_names.add(bound)
                elif m == "lux_tpu.obs.dtrace" and (
                        a.name in _DTRACE_MEMBERS):
                    dtrace_names.add(bound)
    return (obs_mods, ring_mods, rec_names, fetch_names, dtrace_mods,
            dtrace_names)


def _is_dtrace_call(cn: str, dtrace_mods: Set[str],
                    dtrace_names: Set[str]) -> bool:
    if cn in dtrace_names:
        return True
    head, _, member = cn.rpartition(".")
    return member in _DTRACE_MEMBERS and (
        head in dtrace_mods or head == "lux_tpu.obs.dtrace")


def _is_recorder_call(cn: str, obs_mods: Set[str], ring_mods: Set[str],
                      rec_names: Set[str], fetch_names: Set[str]) -> bool:
    if cn in rec_names or cn in fetch_names:
        return True
    head, _, member = cn.rpartition(".")
    if member in _RECORDER_MEMBERS and (
            head in obs_mods
            or head in ("lux_tpu.obs", "lux_tpu.obs.recorder")):
        return True
    return member in _RING_FETCH_MEMBERS and (
        head in ring_mods or head == "lux_tpu.obs.ring")


def _is_ring_fetch(cn: str, ring_mods: Set[str],
                   fetch_names: Set[str]) -> bool:
    head, _, member = cn.rpartition(".")
    if head:
        return member in _RING_FETCH_MEMBERS and (
            head in ring_mods or head == "lux_tpu.obs.ring")
    return cn in fetch_names


def _compiled_loop_names(mod: Module) -> Set[str]:
    """Names bound from a ``compile_*`` factory call anywhere in the
    module (``loop = compile_push_chunk(...)``) — calling such a name is
    driving a compiled runner, the repo's dominant push idiom, and
    LUX-O003 must see it the same as a ``run_*`` entry point."""
    names: Set[str] = set()
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        last = call_name(node.value).rpartition(".")[2]
        if not last.startswith(("compile_", "_compile_")):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                names.add(tgt.id)
    return names


def _is_runner_call(cn: str, loop_names: Set[str] = frozenset()) -> bool:
    last = cn.rpartition(".")[2]
    return last in _RUNNER_SUFFIXES or cn in loop_names


class ObsChecker(Checker):
    family = "observability"
    name = "obs"

    def run(self, mod: Module) -> Iterable[Finding]:
        out: List[Finding] = []
        (obs_mods, ring_mods, rec_names, fetch_names, dtrace_mods,
         dtrace_names) = _obs_aliases(mod)
        traced = set(traced_functions(mod))

        for fn in traced:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                cn = call_name(node)
                if cn in _SYNC_CALLS or (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in _SYNC_METHODS):
                    out.append(self.finding(
                        mod, node, "LUX-O001",
                        f"host sync `{cn or node.func.attr}` inside traced "
                        f"body `{fn.name}` — syncs belong outside the "
                        "compiled loop (fetch once at run end)"))
                elif _is_recorder_call(cn, obs_mods, ring_mods,
                                       rec_names, fetch_names):
                    out.append(self.finding(
                        mod, node, "LUX-O002",
                        f"flight-recorder host API `{cn}` inside traced "
                        f"body `{fn.name}` — spans/points run at trace "
                        "time here; carry a telemetry ring (ring_push) "
                        "instead"))
                elif cn in _CALLBACK_CALLS:
                    out.append(self.finding(
                        mod, node, "LUX-O004",
                        f"host callback `{cn}` inside traced body "
                        f"`{fn.name}` — a device->host round trip per "
                        "execution; remove before shipping"))
                elif _is_dtrace_call(cn, dtrace_mods, dtrace_names):
                    out.append(self.finding(
                        mod, node, "LUX-O005",
                        f"trace-context API `{cn}` inside traced body "
                        f"`{fn.name}` — contexts are host metadata; "
                        "minted here it runs at TRACE time and bakes "
                        "one span id into the compiled program (mint/"
                        "propagate outside jit, docs/OBSERVABILITY.md)"))

        # LUX-O003: ring fetch in a Python loop that drives a compiled
        # runner — the per-iteration-fence anti-pattern, host side
        loop_names = _compiled_loop_names(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            calls = [n for n in ast.walk(node) if isinstance(n, ast.Call)]
            if not any(_is_runner_call(call_name(c), loop_names)
                       for c in calls):
                continue
            for c in calls:
                cn = call_name(c)
                if _is_ring_fetch(cn, ring_mods, fetch_names):
                    out.append(self.finding(
                        mod, c, "LUX-O003",
                        f"per-iteration telemetry fetch `{cn}` inside a "
                        "driving loop — the ring contract is ONE host "
                        "fetch after the run (docs/OBSERVABILITY.md)"))
        return out
