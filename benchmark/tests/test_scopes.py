"""Device seconds by engine scope and gap labels on a small synthetic
trace of known answer; the program-counter reader; the window's
counters from ``benchmark/scopes.py`` on a CPU rehearsal.  No number
here is a device number."""
import json
import types

import pytest
from jax.profiler import ProfileData

from benchmark import harness, scopes

# times in ns.  Device: module jit_loop over [0, 20000), jit_other over
# [20000, 30000); in jit_loop a loop [1000, 9000) holding %fusion.1
# [1000, 3000), %fusion.2 [3000, 4000) and %copy.3 [4000, 5000); in
# jit_other %fusion.1 [21000, 22000) and %fusion.4 [24000, 27000).
# Host: the window [1000, 25000) inside job.run [1000, 25000), push.init
# [9000, 12000), job.fetch [22000, 25000).
_TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 1 offset_ps: 21000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 24000000 duration_ps: 3000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 7 offset_ps: 20000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = s32[8]{0} fusion(s32[8]{0} %q), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8]{0} copy(f32[8]{0} %p)" } }
  event_metadata { key: 4 value { id: 4 name: "%while.1 = (s32[]{:T(128)}) while((s32[]) %t), body=%b" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 6 value { id: 6 name: "jit_loop(1234)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_other(5678)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 24000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 24000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 22000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "job.run" } }
  event_metadata { key: 3 value { id: 3 name: "push.init" } }
  event_metadata { key: 4 value { id: 4 name: "job.fetch" } }
}
"""

_SCOPES = {"jit_loop": {"%fusion.1": "push.dense", "%fusion.2": "push.sparse",
                        "%while.1": "other"},
           "jit_other": {"%fusion.1": "pull.gather",
                         "%fusion.4": "pull.reduce"}}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(loop)/while/body/push.plan/add", "push.plan"),
    ("jit(f)/while/body/closed_call/vmap(pull.gather)/gather",
     "pull.gather"),
    ("jit(loop)/while/body/push.dense/vmap(pull.reduce)/scatter-add",
     "pull.reduce"),
    ("jit(f)/while/cond/lt", "other"),
    ("jit(f)/repull.gather/add", "other"),
])
def test_scope_of_innermost(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_hlo_scopes_reads_instruction_op_names():
    text = "\n".join([
        "HloModule jit_loop, is_scheduled=true",
        "",
        "%fused_computation.3 (param_0: f32[8]) -> f32[8] {",
        "  %param_0 = f32[8]{0} parameter(0)",
        '  ROOT %m = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name='
        '"jit(loop)/while/body/push.dense/vmap(push.x)/mul"}',
        "}",
        "",
        "ENTRY %main (a: f32[8], i: s32[], n: s32[]) -> f32[8] {",
        '  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls='
        '%fused_computation.3, metadata={op_name="jit(loop)/while/body/'
        'push.dense/gather" stack_frame_id=2}',
        '  %lt.0 = pred[] compare(%i, %n), direction=LT, metadata='
        '{op_name="jit(loop)/while/cond/lt"}',
        "  ROOT %t = (f32[8]) tuple(%fusion.1)",
        "}"])
    assert scopes.hlo_scopes(text) == {
        "%param_0": "other", "%m": "push.x", "%fusion.1": "push.dense",
        "%lt.0": "other", "%t": "push.dense"}


def test_hlo_scopes_names_ops_the_compiler_left_unnamed():
    """A scatter rewritten into an unnamed fusion takes its fused root's
    scope; the root, unnamed too, the latest phase it reads, through the
    fused parameters to the fusion's operands."""
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused_computation.11 (param_0.1: s32[8], param_1.2: f32[8]) -> "
        "f32[4] {",
        "  %param_0.1 = s32[8]{0} parameter(0)",
        "  %param_1.2 = f32[8]{0} parameter(1)",
        '  %mul.1 = f32[8]{0} multiply(%param_1.2, %param_1.2), metadata='
        '{op_name="jit(f)/while/body/vmap(pull.gather)/mul"}',
        "  ROOT %scatter.6 = f32[4]{0} scatter(%constant.1, %param_0.1, "
        "%mul.1), to_apply=%add",
        "}",
        "ENTRY %main (p: f32[8], q: s32[8]) -> f32[4] {",
        '  %idx = s32[8]{0} add(%q, %q), metadata={op_name="jit(f)/while/'
        'body/vmap(pull.reduce)/add"}',
        "  %sel = s32[8]{0} copy(%idx)",
        '  %g = f32[8]{0} gather(%p, %q), metadata={op_name="jit(f)/while/'
        'body/vmap(pull.gather)/gather"}',
        "  %fusion.17 = f32[4]{0} fusion(%sel, %g), kind=kCustom, "
        "calls=%fused_computation.11",
        '  ROOT %out = f32[4]{0} add(%fusion.17, %fusion.17), metadata='
        '{op_name="jit(f)/while/body/vmap(pull.apply)/add"}',
        "}"])
    got = scopes.hlo_scopes(text)
    assert got["%fusion.17"] == got["%scatter.6"] == "pull.reduce"
    assert got["%sel"] == got["%param_0.1"] == "pull.reduce"
    assert got["%param_1.2"] == got["%mul.1"] == "pull.gather"
    assert got["%out"] == "pull.apply"


def test_device_scopes_attributes_leaf_ops_by_module():
    got = scopes.device_scopes(ProfileData.from_text_proto(_TEXT), _SCOPES)
    # the loop is left out; %copy.3 has no scope; %fusion.1 is looked up
    # in the module it ran in; %fusion.4 is cut at the window's end
    assert got == {"push.dense": pytest.approx(2e-6),
                   "push.sparse": pytest.approx(1e-6),
                   "other": pytest.approx(1e-6),
                   "pull.gather": pytest.approx(1e-6),
                   "pull.reduce": pytest.approx(1e-6)}


def test_gap_labels_prefer_program_spans():
    gaps = scopes.label_gaps(ProfileData.from_text_proto(_TEXT))
    # [9000, 21000): push.init covers 3000 ns, job.run all of it;
    # [22000, 24000): job.fetch and job.run cover it alike
    assert gaps == [("push.init", pytest.approx(12e-6)),
                    ("job.fetch", pytest.approx(2e-6))]


@pytest.fixture
def recorder(tmp_path):
    from lux_tpu import obs
    from lux_tpu.obs.recorder import Recorder

    rec = Recorder(run_id="bench", root=str(tmp_path), enabled=False)
    old = obs.install(rec)
    yield rec
    obs.install(old)


def _read(name, **run):
    return harness._module("layers", name).read(
        types.SimpleNamespace(**run))


@pytest.mark.parametrize("app,summary,counts,want", [
    ("bfs", object(), (2, 2), 2.0),
    ("bfs", object(), (), None),
    ("bfs", None, (2, 2), None),
    ("pagerank", object(), (2, 2), None),
])
def test_dense_rounds_reader(recorder, app, summary, counts, want):
    for n in counts:
        recorder.count("push.dense_rounds", n)
    assert _read("push.dense_rounds", app=app, summary=summary) == want


@pytest.mark.parametrize("app,summary,counts,want", [
    # the warm-up traversal and two in the window, 300 arcs walked each,
    # 2 x 120 useful edges in the window: 240 of 300 arcs a traversal
    ("bfs", object(), (300, 300, 300), 80.0),
    ("bfs", object(), (300,), None),  # the warm-up alone
    ("bfs", object(), (), None),
    ("bfs", None, (300, 300, 300), None),
    ("pagerank", object(), (300, 300, 300), None),
])
def test_walk_efficiency_reader(recorder, app, summary, counts, want):
    for n in counts:
        recorder.count("push.arcs_walked", n)
    got = _read("push.walk_efficiency", app=app, summary=summary,
                edges=240)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("metric,counter", [
    ("push.dense_rounds", "push.dense_rounds"),
    ("push.walk_efficiency", "push.arcs_walked"),
])
def test_counter_reader_without_program_counters(recorder, monkeypatch,
                                                 metric, counter):
    from lux_tpu import obs

    for _ in range(3):
        recorder.count(counter, 2)
    monkeypatch.delattr(obs, "counters")
    assert _read(metric, app="bfs", summary=object(), edges=2) is None


@pytest.mark.parametrize("cell,counter", [
    ("bfs-graph500-22.one_root", "push.dense_rounds"),
    ("pr-graph500-22.back_to_back", "pull.iterations"),
])
def test_cli_counts_the_window_alone(recorder, capsys, monkeypatch, cell,
                                     counter):
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "true")
    assert scopes.main(["--workload", cell, "--seed", str(2**31 + 5),
                        "--seconds", "0.5", "--cpu-rehearsal", "9"]) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    (window,) = [ln for ln in lines if ln.get("phase") == "window"]
    calls = lines[-1]["program"][counter][0]
    # the warm-up job ran too, before the window: its count is left out
    assert calls == window["jobs"]
    assert recorder.counters(counter)[counter][0] == window["jobs"] + 1
    assert lines[-1]["scopes"] is None  # no device trace on the CPU
