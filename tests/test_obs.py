"""luxtrace (lux_tpu.obs) tests: recorder span semantics + thread
safety, on-device telemetry rings (bitwise no-op vs telemetry-off,
donation, retrace/HBM neutrality), the LUX-O checker family, the
luxview/obs_span CLIs on seeded event logs, the Prometheus dump,
recorder counters, spans on the profiler's host plane, and the engines'
named scopes."""
import glob
import importlib.util
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lux_tpu import obs
from lux_tpu.obs import ring as obs_ring
from lux_tpu.obs.recorder import Recorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_events(run_dir):
    evs = []
    for fn in sorted(os.listdir(run_dir)):
        if fn.startswith("events-") and fn.endswith(".jsonl"):
            with open(os.path.join(run_dir, fn), encoding="utf-8") as f:
                evs.extend(json.loads(ln) for ln in f if ln.strip())
    return evs


@pytest.fixture
def rec(tmp_path):
    r = Recorder(run_id="trun", root=str(tmp_path), enabled=True)
    old = obs.install(r)
    yield r
    r.close()
    obs.install(old)


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------


def test_span_nesting_and_file(rec, tmp_path):
    with obs.span("outer", a=1) as sp_out:
        with obs.span("inner") as sp_in:
            pass
        sp_out.set(banked=True)
    assert sp_out.dur >= sp_in.dur >= 0.0
    evs = read_events(rec.run_dir())
    assert evs[0]["e"] == "m" and evs[0]["run"] == "trun"
    begins = {e["n"]: e for e in evs if e["e"] == "b"}
    ends = {e["s"]: e for e in evs if e["e"] == "e"}
    # nested span's parent is the outer's sid; attrs land begin/end
    assert begins["inner"]["p"] == begins["outer"]["s"]
    assert begins["outer"]["p"] is None
    assert begins["outer"]["a"] == {"a": 1}
    assert ends[begins["outer"]["s"]]["a"] == {"banked": True}
    assert all(ends[s]["ok"] for s in ends)
    # crash-safety: begin events precede their end events in file order
    order = [e["e"] for e in evs]
    assert order == ["m", "b", "b", "e", "e"]


def test_span_exception_marks_not_ok(rec):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    evs = read_events(rec.run_dir())
    (end,) = [e for e in evs if e["e"] == "e"]
    assert end["ok"] is False
    # failed spans stay in the event log but NOT in the aggregate: the
    # totals are the one clock behind plan_build_seconds/phases, and a
    # failed plan.load (rebuilt under plan.build) must not drift them
    assert rec.total_count("boom") == 0


def test_sid_prefix_unique_per_recorder(tmp_path):
    """pid reuse across a battery must not collide sids in the merged
    timeline — two same-pid recorders get distinct per-process tokens."""
    a = Recorder(run_id="r", root=str(tmp_path), enabled=False)
    b = Recorder(run_id="r", root=str(tmp_path), enabled=False)
    with a.span("x") as sa, b.span("x") as sb:
        pass
    assert sa.sid != sb.sid
    assert sa.sid.startswith(f"{os.getpid()}-")


def test_point_and_totals(rec):
    obs.point("marker", k=3)
    with obs.span("plan.build"):
        pass
    with obs.span("plan.build"):
        pass
    assert rec.total_count("plan.build") == 2
    assert rec.total_seconds("plan.build") >= 0.0
    assert set(rec.totals("plan.")) == {"plan.build"}
    rec.reset_totals("plan.")
    assert rec.total_count("plan.build") == 0
    assert any(e["e"] == "p" and e["n"] == "marker"
               for e in read_events(rec.run_dir()))


def test_recorder_thread_safety(rec):
    n_threads, n_spans = 8, 50

    def work(i):
        for k in range(n_spans):
            with obs.span(f"t{i}", k=k):
                with obs.span(f"t{i}.inner"):
                    pass

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = read_events(rec.run_dir())
    begins = [e for e in evs if e["e"] == "b"]
    ends = [e for e in evs if e["e"] == "e"]
    assert len(begins) == len(ends) == 2 * n_threads * n_spans
    # sids unique; every inner's parent is a same-thread outer (the span
    # stack is thread-local, so cross-thread nesting cannot happen)
    sids = [e["s"] for e in begins]
    assert len(set(sids)) == len(sids)
    name_of = {e["s"]: e["n"] for e in begins}
    for e in begins:
        if e["n"].endswith(".inner"):
            assert name_of[e["p"]] == e["n"][:-len(".inner")]
    for i in range(n_threads):
        assert rec.total_count(f"t{i}") == n_spans


def test_disabled_recorder_still_aggregates(tmp_path):
    r = Recorder(run_id="off", root=str(tmp_path / "x"), enabled=False)
    with r.span("s"):
        pass
    assert r.total_count("s") == 1
    assert r.log_path is None
    assert not (tmp_path / "x").exists()


def test_untrusted_dir_degrades_to_memory(tmp_path):
    target = tmp_path / "occupied"
    target.write_text("not a dir")
    r = Recorder(run_id="deg", root=str(target), enabled=True)
    with r.span("s"):
        pass  # must not raise
    assert r.log_path is None
    assert r.total_count("s") == 1


def test_run_id_env_inheritance(tmp_path, monkeypatch):
    monkeypatch.setenv("LUX_OBS_RUN_ID", "from_env_123")
    r = Recorder(root=str(tmp_path))
    assert r.run_id == "from_env_123"


def test_retention_sweeps_only_old_runs(tmp_path, monkeypatch):
    """The always-on recorder must bound its own disk footprint: keep
    the newest LUX_OBS_KEEP run dirs, never a recently-written one, and
    never the current run."""
    # the package re-exports the recorder() accessor under the module's
    # name, so resolve the MODULE explicitly (obs_span.py idiom)
    rmod = importlib.import_module("lux_tpu.obs.recorder")

    root = tmp_path / "obs"
    root.mkdir(mode=0o700)
    old = time.time() - 2 * rmod.SWEEP_MIN_AGE_S
    for i in range(4):
        d = root / f"run{i}"
        d.mkdir(mode=0o700)
        (d / "events-1.jsonl").write_text("{}\n")
        # run3 is the newest stale dir; run0 the oldest
        os.utime(d / "events-1.jsonl", (old + i, old + i))
        os.utime(d, (old + i, old + i))
    fresh = root / "live"
    fresh.mkdir(mode=0o700)
    (fresh / "events-9.jsonl").write_text("{}\n")  # now-mtime: in-age guard

    monkeypatch.setenv("LUX_OBS_KEEP", "3")
    r = Recorder(run_id="cur", root=str(root), enabled=True)
    with r.span("s"):
        pass
    r.close()
    survivors = sorted(p.name for p in root.iterdir())
    # keep=3 = current + 2 newest others; "live" survives on age alone,
    # so the stale dirs shrink to the single newest one
    assert "cur" in survivors and "live" in survivors
    assert "run3" in survivors
    assert not any(n in survivors for n in ("run0", "run1", "run2"))

    # keep<=0 disables the sweep entirely
    monkeypatch.setenv("LUX_OBS_KEEP", "0")
    r2 = Recorder(run_id="cur2", root=str(root), enabled=True)
    with r2.span("s"):
        pass
    r2.close()
    assert "run3" in {p.name for p in root.iterdir()}


# ---------------------------------------------------------------------------
# on-device telemetry rings
# ---------------------------------------------------------------------------


def _pull_setup(scale=8, parts=2, routed=False):
    from lux_tpu.engine import pull
    from lux_tpu.graph import generate
    from lux_tpu.graph.shards import build_pull_shards
    from lux_tpu.models.pagerank import PageRankProgram
    from lux_tpu.ops import expand as E

    g = generate.rmat(scale, 8, seed=17)
    shards = build_pull_shards(g, parts)
    dev = jax.tree.map(jnp.asarray, shards.arrays)
    prog = PageRankProgram(nv=shards.spec.nv)
    s0 = pull.init_state(prog, dev)
    route = E.plan_expand_shards(shards, pf=True) if routed else None
    return pull, prog, shards, dev, s0, route


@pytest.mark.parametrize("routed", [False, True])
def test_ring_pull_fixed_bitwise_noop(routed):
    """Telemetry-on == telemetry-off BITWISE on the result state, for
    the direct and the routed-pf pull (the ring is pure extra output)."""
    pull, prog, shards, dev, s0, route = _pull_setup(routed=routed)
    ref = pull.run_pull_fixed(prog, shards.spec, dev, s0, 6,
                              method="scan", route=route)
    out, rg = pull.run_pull_fixed(
        prog, shards.spec, dev, s0, 6, method="scan", route=route,
        telemetry=obs_ring.new_ring("pull_fixed"))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    rows, n = obs_ring.ring_rows(rg)
    assert n == 6 and rows.shape == (6, 2)
    # recorded column 0 is the iteration index, in order
    np.testing.assert_array_equal(rows[:, 0], np.arange(6))
    # PageRank's residual curve decreases over the tail
    assert rows[-1, 1] < rows[0, 1]


def test_ring_pull_until_bitwise_noop():
    from lux_tpu.models import components as cc_model
    from lux_tpu.models.components import MaxLabelProgram

    pull, _, shards, dev, _, _ = _pull_setup()
    prog = MaxLabelProgram()
    s0 = pull.init_state(prog, dev)
    ref, it_ref = pull.run_pull_until(prog, shards.spec, dev, s0, 50,
                                      cc_model.active_count, method="scan")
    out, it, rg = pull.run_pull_until(
        prog, shards.spec, dev, s0, 50, cc_model.active_count,
        method="scan", telemetry=obs_ring.new_ring("pull_until"))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert int(it) == int(it_ref)
    rows, n = obs_ring.ring_rows(rg)
    assert n == int(it)
    # the loop stops when the active count hits 0 — the ring's last row
    # is that 0 (the recorded convergence event)
    assert rows[-1, 1] == 0
    assert (rows[:-1, 1] > 0).all()


def test_ring_push_bitwise_noop():
    from lux_tpu.engine import push
    from lux_tpu.graph import generate
    from lux_tpu.graph.push_shards import build_push_shards
    from lux_tpu.models import sssp

    g = generate.rmat(8, 8, seed=31)
    sh = build_push_shards(g, 2)
    prog = sssp.SSSPProgram(nv=g.nv, start=0)
    ref_state, ref_it, ref_edges = push.run_push(prog, sh)
    state, it, edges, rg = push.run_push(
        prog, sh, telemetry=obs_ring.new_ring("push"))
    np.testing.assert_array_equal(np.asarray(ref_state), np.asarray(state))
    assert int(it) == int(ref_it)
    assert push.edges_total(edges) == push.edges_total(ref_edges)
    rows, n = obs_ring.ring_rows(rg)
    assert n == int(it) and rows.shape[1] == 4
    # per-round traversed-edge deltas sum to the engine's exact counter
    assert int(rows[:, 2].sum()) == push.edges_total(edges)
    # round 0's frontier is the start vertex alone
    assert rows[0, 1] == 1


def test_ring_wraparound_keeps_tail():
    pull, prog, shards, dev, s0, _ = _pull_setup()
    out, rg = pull.run_pull_fixed(
        prog, shards.spec, dev, s0, 10, method="scan",
        telemetry=obs_ring.new_ring("pull_fixed", cap=4))
    rows, n = obs_ring.ring_rows(rg)
    assert n == 10 and rows.shape == (4, 2)
    # the LAST cap rows, in push order
    np.testing.assert_array_equal(rows[:, 0], np.arange(6, 10))


def test_ring_telemetry_retrace_and_hbm_neutral():
    """The ring adds no accounted HBM pass (plan-derived accounting is
    untouched) and no kernel launches: the telemetry jaxpr contains
    exactly the same pallas_call count as the bare loop, and the routed
    sweep accounting is identical before/after a telemetry run."""
    from lux_tpu.utils import roofline

    pull, prog, shards, dev, s0, route = _pull_setup(routed=True)
    passes_before = roofline.routed_hbm_passes(route[0], "scan")

    def count_pallas(fn, *args, **kw):
        jaxpr = jax.make_jaxpr(fn, static_argnums=())(*args, **kw)
        n = 0
        stack = [jaxpr.jaxpr]
        while stack:
            j = stack.pop()
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    n += 1
                for v in eqn.params.values():
                    if hasattr(v, "jaxpr"):
                        stack.append(v.jaxpr)
                    elif isinstance(v, (list, tuple)):
                        stack.extend(x.jaxpr for x in v
                                     if hasattr(x, "jaxpr"))
            for sub in getattr(j, "jaxprs", ()):
                stack.append(sub)
        return n

    rs, ra = route
    ra_dev = jax.tree.map(jnp.asarray, ra)

    def bare(state):
        return pull._pull_fixed_fn(prog, shards.spec, 3, "scan", dev,
                                   state, None, route_static=rs,
                                   route_arrays=ra_dev, interpret=True)

    def with_ring(state, rg):
        return pull._pull_fixed_fn(prog, shards.spec, 3, "scan", dev,
                                   state, rg, route_static=rs,
                                   route_arrays=ra_dev, interpret=True)

    n_bare = count_pallas(bare, s0)
    n_tel = count_pallas(with_ring, s0, obs_ring.new_ring("pull_fixed"))
    assert n_tel == n_bare > 0
    # and the accounted sweeps did not move
    out, rg = pull.run_pull_fixed(
        prog, shards.spec, dev, s0, 3, method="scan", route=route,
        telemetry=obs_ring.new_ring("pull_fixed"))
    assert roofline.routed_hbm_passes(route[0], "scan") == passes_before


def test_ring_donation_consumes_buffers():
    """donate=True with a telemetry ring: the state AND the ring input
    buffers are consumed (single copy in HBM), results bitwise equal."""
    pull, prog, shards, dev, s0, _ = _pull_setup()
    ref = pull.run_pull_fixed(prog, shards.spec, dev, s0, 4, method="scan")
    s0_d = jnp.array(s0)  # a private copy to donate
    ring_in = jax.tree.map(jnp.asarray, obs_ring.new_ring("pull_fixed"))
    out, rg = pull.run_pull_fixed(prog, shards.spec, dev, s0_d, 4,
                                  method="scan", donate=True,
                                  telemetry=ring_in)
    jax.block_until_ready(out)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert s0_d.is_deleted()
    assert ring_in.buf.is_deleted()
    rows, n = obs_ring.ring_rows(rg)
    assert n == 4


def test_push_telemetry_donate_consumes():
    from lux_tpu.engine import push
    from lux_tpu.graph import generate
    from lux_tpu.graph.push_shards import build_push_shards
    from lux_tpu.models import sssp

    g = generate.rmat(8, 8, seed=31)
    sh = build_push_shards(g, 2)
    prog = sssp.SSSPProgram(nv=g.nv, start=0)
    ref_state, ref_it, ref_edges = push.run_push(prog, sh)
    loop = push.compile_push_chunk(prog, sh.pspec, sh.spec, "scan",
                                   donate=True, telemetry=True)
    arrays, parrays, carry0 = push.push_init(prog, sh)
    ring_in = jax.tree.map(jnp.asarray, obs_ring.new_ring("push"))
    out, rg = loop(arrays, parrays, carry0, jnp.int32(50), ring_in)
    jax.block_until_ready(out.state)
    np.testing.assert_array_equal(np.asarray(ref_state),
                                  np.asarray(out.state))
    assert carry0.state.is_deleted()
    assert ring_in.buf.is_deleted()


def test_emit_ring_point(rec):
    pull, prog, shards, dev, s0, _ = _pull_setup()
    _, rg = pull.run_pull_fixed(
        prog, shards.spec, dev, s0, 3, method="scan",
        telemetry=obs_ring.new_ring("pull_fixed"))
    obs_ring.emit_ring("pull_fixed", rg, app="pagerank")
    (p,) = [e for e in read_events(rec.run_dir()) if e["e"] == "p"]
    assert p["n"] == "telemetry.ring"
    assert p["a"]["kind"] == "pull_fixed" and p["a"]["n"] == 3
    assert p["a"]["cols"] == ["it", "residual_l1"]
    assert len(p["a"]["rows"]) == 3


# ---------------------------------------------------------------------------
# LUX-O checker family
# ---------------------------------------------------------------------------

_LUXO_BAD = '''
import jax
from lux_tpu import obs
from lux_tpu.obs import ring as obs_ring

@jax.jit
def f(x):
    jax.block_until_ready(x)          # O001
    obs.point("inside", v=1)          # O002
    jax.debug.print("x={}", x)        # O004
    return x + 1

def driver(prog, spec, arrays, state, ring):
    for k in range(10):
        state = run_pull_fixed(prog, spec, arrays, state, k)
        rows, n = obs_ring.ring_rows(ring)   # O003
    return state
'''

_LUXO_CLEAN = '''
import jax
from lux_tpu import obs
from lux_tpu.obs import ring as obs_ring

@jax.jit
def f(x, ring):
    return x + 1, obs_ring.ring_push(ring, 0, x.sum())

def driver(prog, spec, arrays, state, ring):
    with obs.span("pull.chunk", k=10):
        for k in range(10):
            state = run_pull_fixed(prog, spec, arrays, state, k)
        jax.block_until_ready(state)
    rows, n = obs_ring.ring_rows(ring)  # ONE fetch, after the loop
    obs_ring.emit_ring("pull_fixed", ring)
    return state
'''


def _luxo_run(tmp_path, source, name):
    from lux_tpu.analysis import check_paths
    from lux_tpu.analysis.obs import ObsChecker

    p = tmp_path / name
    p.write_text(source)
    return check_paths([str(p)], str(tmp_path), checkers=[ObsChecker()])


def test_luxo_seeded_fixture_fires(tmp_path):
    findings = _luxo_run(tmp_path, _LUXO_BAD, "bad.py")
    codes = sorted(f.code for f in findings)
    assert codes == ["LUX-O001", "LUX-O002", "LUX-O003", "LUX-O004"]


def test_luxo_clean_twin(tmp_path):
    assert _luxo_run(tmp_path, _LUXO_CLEAN, "clean.py") == []


def test_luxo_registered_in_all_checkers():
    from lux_tpu.analysis import ALL_CHECKERS, FAMILIES

    assert "observability" in FAMILIES
    assert any(type(c).__name__ == "ObsChecker" for c in ALL_CHECKERS)


def test_luxo_renamed_import_still_caught(tmp_path):
    src = (
        "import jax\n"
        "from lux_tpu.obs.ring import ring_rows as rr\n\n"
        "def body(c):\n"
        "    return rr(c)\n\n"
        "out = jax.lax.while_loop(lambda c: True, body, 0)\n"
    )
    findings = _luxo_run(tmp_path, src, "renamed.py")
    assert [f.code for f in findings] == ["LUX-O002"]


def test_luxo_compiled_loop_idiom_caught(tmp_path):
    """The repo's dominant push idiom drives the callable returned by a
    compile_* factory, not a run_* entry point — O003 must see it."""
    src = (
        "from lux_tpu.obs import ring as obs_ring\n\n"
        "def driver(push, prog, pspec, spec, arrays, parrays, carry, ring):\n"
        "    loop = push.compile_push_chunk(prog, pspec, spec, 'scan')\n"
        "    while int(carry.active) > 0:\n"
        "        carry, ring = loop(arrays, parrays, carry, 8, ring)\n"
        "        rows, n = obs_ring.ring_rows(ring)   # per-chunk fence\n"
        "    return carry\n"
    )
    findings = _luxo_run(tmp_path, src, "loopidiom.py")
    assert [f.code for f in findings] == ["LUX-O003"]

    clean = (
        "from lux_tpu.obs import ring as obs_ring\n\n"
        "def driver(push, prog, pspec, spec, arrays, parrays, carry, ring):\n"
        "    loop = push.compile_push_chunk(prog, pspec, spec, 'scan')\n"
        "    while int(carry.active) > 0:\n"
        "        carry, ring = loop(arrays, parrays, carry, 8, ring)\n"
        "    rows, n = obs_ring.ring_rows(ring)  # ONE fetch, after\n"
        "    return carry\n"
    )
    assert _luxo_run(tmp_path, clean, "loopidiom_clean.py") == []


# ---------------------------------------------------------------------------
# luxview + obs_span CLIs
# ---------------------------------------------------------------------------


def _seed_event_log(tmp_path):
    """A deterministic multi-section event log (injected clock)."""
    t = iter(float(x) for x in range(100))
    r = Recorder(run_id="golden", root=str(tmp_path),
                 clock=lambda: next(t), enabled=True)
    with r.span("step.micro_race", timeout_s=300) as sp:
        with r.span("compile.warm"):
            pass
        sp.set(rc=0)  # end attrs (Span.set / obs_span --rc) must render
    r.point("telemetry.ring", kind="pull_fixed",
            cols=["it", "residual_l1"], n=3,
            rows=[[0, 0.5], [1, 0.25], [2, 0.125]], app="pagerank")
    r.point("serve.metrics", completed=64, timeouts=0, rejected=1,
            batches=2, qps=880.0, latency_ms={"p50": 3.1, "p99": 9.7})
    r.point("bench.row", metric="pagerank_gteps_rmat18_1chip",
            value=1.23, unit="GTEPS", method="scan")
    # an OPEN span: the process "died" inside
    r.span("step.bench_race").__enter__()
    r.close()
    return os.path.join(str(tmp_path), "golden")


def test_luxview_golden_report(tmp_path, capsys):
    run_dir = _seed_event_log(tmp_path)
    luxview = _load_tool("luxview")
    rc = luxview.main([run_dir])
    out = capsys.readouterr().out
    assert rc == 0
    assert "# luxtrace report — run golden" in out
    # post-mortem: the OPEN span is called out
    assert "step.bench_race" in out and "OPEN" in out
    # waterfall: nesting + durations on the injected clock (1s ticks)
    assert "step.micro_race" in out and "compile.warm" in out
    assert "[timeout_s=300, rc=0]" in out
    # telemetry curve, serve, bench sections all render
    assert "ring: pull_fixed" in out and "residual_l1" in out
    assert "qps=880.0" in out and "p99=9.7" in out
    assert "pagerank_gteps_rmat18_1chip" in out
    assert out.rstrip().endswith("run_id: golden")


def test_luxview_list_and_missing(tmp_path, capsys):
    luxview = _load_tool("luxview")
    assert luxview.main(["--root", str(tmp_path), "--list"]) == 0
    assert luxview.main(["--root", str(tmp_path), "nope"]) == 2


def test_luxview_out_file(tmp_path, capsys):
    run_dir = _seed_event_log(tmp_path)
    out_md = tmp_path / "window_report.md"
    luxview = _load_tool("luxview")
    assert luxview.main([run_dir, "--out", str(out_md)]) == 0
    assert "run_id: golden" in out_md.read_text()


def test_obs_span_cli_roundtrip(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("LUX_OBS_DIR", str(tmp_path))
    monkeypatch.setenv("LUX_OBS_RUN_ID", "shellrun")
    obs_span = _load_tool("obs_span")
    assert obs_span.main(["begin", "step.probe", "timeout_s=60"]) == 0
    sid = capsys.readouterr().out.strip()
    assert sid
    assert obs_span.main(["end", sid, "--rc", "0"]) == 0
    assert obs_span.main(["point", "battery.abort", "reason=test"]) == 0
    evs = read_events(str(tmp_path / "shellrun"))
    kinds = [e["e"] for e in evs]
    assert kinds == ["m", "b", "e", "p"]
    assert evs[1]["s"] == sid and evs[1]["a"] == {"timeout_s": 60}
    assert evs[2]["ok"] is True
    # a failed step records rc and ok=False
    assert obs_span.main(["begin", "step.dead"]) == 0
    sid2 = capsys.readouterr().out.strip()
    assert obs_span.main(["end", sid2, "--rc", "124"]) == 0
    evs = read_events(str(tmp_path / "shellrun"))
    assert evs[-1]["ok"] is False and evs[-1]["a"]["rc"] == 124


def test_obs_span_begin_empty_sid_on_degrade(tmp_path, monkeypatch,
                                             capsys):
    """An unusable log dir must print an EMPTY sid (the documented
    degrade contract) so a caller's [ -n "$sid" ] guards skip the
    end/point spawns instead of appending into the void."""
    bad = tmp_path / "occupied"
    bad.write_text("not a dir")
    monkeypatch.setenv("LUX_OBS_DIR", str(bad))
    monkeypatch.setenv("LUX_OBS_RUN_ID", "degraded")
    obs_span = _load_tool("obs_span")
    assert obs_span.main(["begin", "step.x"]) == 0
    assert capsys.readouterr().out.strip() == ""


# ---------------------------------------------------------------------------
# serve metrics: Prometheus dump + snapshots
# ---------------------------------------------------------------------------


def test_prometheus_dump_format():
    from lux_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    for ms in (1, 2, 5, 50):
        m.record_done(latency_s=ms / 1e3, wait_s=ms / 2e3, traversed=100)
    m.record_batch(q=8, real=4, warm=True, service_s=0.004)
    m.record_rejected()
    m.sample_queue_depth(7)
    text = m.dump(elapsed_s=2.0,
                  cache_stats={"warm_hits": 3, "cold_traces": 1})
    assert "# TYPE lux_serve_requests_completed_total counter" in text
    assert "lux_serve_requests_completed_total 4" in text
    assert "lux_serve_requests_shed_total 1" in text
    assert "lux_serve_queue_depth_max 7" in text
    assert "lux_serve_qps 2.0" in text
    assert "lux_serve_warm_hit_ratio 0.75" in text
    # histogram: cumulative buckets, +Inf == count
    assert 'lux_serve_request_latency_seconds_bucket{le="0.001"} 1' in text
    assert 'lux_serve_request_latency_seconds_bucket{le="0.01"} 3' in text
    assert 'lux_serve_request_latency_seconds_bucket{le="+Inf"} 4' in text
    assert "lux_serve_request_latency_seconds_count 4" in text
    # cumulative monotonicity across all buckets
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
              if "latency_seconds_bucket" in ln]
    assert counts == sorted(counts)


def test_metrics_snapshot_point(rec):
    from lux_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    m.record_done(latency_s=0.003, wait_s=0.001, traversed=10)
    m.emit_snapshot(elapsed_s=1.0)
    (p,) = [e for e in read_events(rec.run_dir()) if e["e"] == "p"]
    assert p["n"] == "serve.metrics"
    assert p["a"]["completed"] == 1 and "latency_ms" in p["a"]


def test_scheduler_periodic_snapshot(rec):
    """Fake-clock pumps cross snapshot_every_s -> serve.metrics points
    land in the event log (first pump only arms the timer)."""
    from lux_tpu.serve.scheduler import MicroBatchScheduler

    class _NoCache:
        def warm_buckets(self, app):
            return ()

    sched = MicroBatchScheduler(_NoCache(), app="sssp",
                                clock=lambda: 0.0)
    sched.snapshot_every_s = 10.0
    sched.step(now=0.0)     # arms
    sched.step(now=5.0)     # within the window: no snapshot
    sched.step(now=11.0)    # fires
    sched.step(now=12.0)    # within
    sched.step(now=22.0)    # fires
    snaps = [e for e in read_events(rec.run_dir())
             if e["e"] == "p" and e["n"] == "serve.metrics"]
    assert len(snaps) == 2


# ---------------------------------------------------------------------------
# recorder counters
# ---------------------------------------------------------------------------


def test_count_calls_and_totals(rec):
    obs.count("push.rounds", 3)
    obs.count("push.rounds", 4)
    obs.count("pull.iterations", 10)
    assert obs.counters() == {"push.rounds": (2, 7),
                              "pull.iterations": (1, 10)}
    assert obs.counters("push.") == {"push.rounds": (2, 7)}


def test_count_device_scalar_resolved_at_read(rec):
    x = jnp.int32(5)
    obs.count("push.dense_rounds", x)
    obs.count("push.arcs_walked", jnp.array([1, 2], jnp.uint32),
              convert=lambda v: int(v[0]) << 32 | int(v[1]))
    # held unread until the counters are read
    assert len(rec._pending) == 2
    got = obs.counters()
    assert rec._pending == []
    assert got["push.dense_rounds"] == (1, 5)
    assert got["push.arcs_walked"] == (1, (1 << 32) + 2)
    assert isinstance(got["push.dense_rounds"][1], int)


def test_count_pending_list_is_bounded(rec, monkeypatch):
    monkeypatch.setattr(importlib.import_module("lux_tpu.obs.recorder"),
                        "PENDING_MAX", 4)
    for _ in range(3):
        obs.count("push.rounds", jnp.int32(2))
    assert len(rec._pending) == 3
    obs.count("push.rounds", jnp.int32(2))  # the fourth reads all back
    assert rec._pending == []
    assert rec._counters["push.rounds"] == [4, 8]


def test_counters_snapshot_difference(rec):
    obs.count("push.rounds", jnp.int32(6))
    before = obs.counters()
    obs.count("push.rounds", jnp.int32(7))
    obs.count("push.dense_rounds", 2)
    after = obs.counters()
    diff = {k: (c - before.get(k, (0, 0))[0], t - before.get(k, (0, 0))[1])
            for k, (c, t) in after.items()}
    assert diff == {"push.rounds": (1, 7), "push.dense_rounds": (1, 2)}


def test_span_on_profiler_host_plane(rec, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with obs.span("push.init"):
        jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    host = [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    assert "push.init" in host
    assert rec.total_count("push.init") == 1


def test_luxo_flags_count_in_traced_body(tmp_path):
    src = (
        "import jax\n"
        "from lux_tpu import obs\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    obs.count('push.rounds', x)\n"
        "    return x + 1\n\n"
        "def host_side(x):\n"
        "    y = f(x)\n"
        "    obs.count('push.rounds', y)  # host side: legal\n"
        "    return y\n"
    )
    findings = _luxo_run(tmp_path, src, "count.py")
    assert [f.code for f in findings] == ["LUX-O002"]


# ---------------------------------------------------------------------------
# engine scopes and push counters
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kron9():
    from lux_tpu.graph import generate

    return generate.rmat(9, 16, seed=23)


@pytest.fixture(scope="module")
def engine_hlo(kron9):
    """Compiled HLO text of the pull loop (PageRank) and the push loop
    (BFS), each holding its ops' op_name metadata."""
    from lux_tpu.engine import methods, pull, push
    from lux_tpu.graph.push_shards import build_push_shards
    from lux_tpu.graph.shards import build_pull_shards
    from lux_tpu.models.pagerank import PageRankProgram
    from lux_tpu.models.sssp import SSSPProgram

    g = kron9
    pr = PageRankProgram(nv=g.nv)
    psh = build_pull_shards(g, 1)
    arrays = jax.tree.map(jnp.asarray, psh.arrays)
    method = methods.resolve_sum("auto", pr.reduce)
    pull_text = pull._pull_fixed_jit.lower(
        pr, psh.spec, 3, method, arrays, pull.init_state(pr, arrays), None,
        interpret=True).compile().as_text()
    bfs = SSSPProgram(nv=g.nv, start=0)
    sh = build_push_shards(g, 1)
    arrays, parrays, carry0 = push.push_init(bfs, sh)
    loop = push.compile_push_chunk(bfs, sh.pspec, sh.spec)
    push_text = loop.lower(arrays, parrays, carry0,
                           jnp.int32(100)).compile().as_text()
    return {"pull": pull_text, "push": push_text}


@pytest.mark.parametrize("scope", [
    "pull.gather", "pull.reduce", "pull.apply",
    "push.plan", "push.dense", "push.sparse", "push.requeue"])
def test_engine_scope_in_op_names(engine_hlo, scope):
    # a scope is one component of the op name, "vmap(...)"-wrapped
    # where the engine vmaps over parts
    text = engine_hlo[scope.split(".")[0]]
    assert re.search(rf'op_name="[^"]*[/(]{re.escape(scope)}[)/]', text)


def test_run_push_counters_match_carry(rec, kron9):
    """run_push records the final carry's rounds, dense rounds and
    walked arcs (ne per dense round plus each sparse round's frontier
    out-edges), and its results are the loop's own, bit for bit."""
    from lux_tpu.engine import push
    from lux_tpu.graph.push_shards import build_push_shards
    from lux_tpu.models.sssp import SSSPProgram

    g = kron9
    root = int(np.argmax(g.out_degrees()))
    prog = SSSPProgram(nv=g.nv, start=root)
    sh = build_push_shards(g, 1)

    # the loop alone, with no recorder call
    arrays, parrays, carry0 = push.push_init(prog, sh)
    loop = push.compile_push_chunk(prog, sh.pspec, sh.spec)
    want = loop(arrays, parrays, carry0, jnp.int32(10_000))

    # the same rounds one by one, for the sparse rounds' walked arcs
    load, comp, update = push.compile_push_phases(prog, sh.pspec, sh.spec)
    arrays, parrays, c = push.push_init(prog, sh)
    sparse_arcs, dense = 0, 0
    while int(c.active) > 0:
        plan = load(parrays, c)
        if bool(plan[3]):
            dense += 1
        else:
            sparse_arcs += int(plan[2][3].sum())
        c = update(arrays, c, comp(arrays, parrays, c, plan), plan)

    state, it, edges = push.run_push(prog, sh)
    np.testing.assert_array_equal(np.asarray(state), np.asarray(want.state))
    np.testing.assert_array_equal(np.asarray(edges), np.asarray(want.edges))
    got = obs.counters("push.")
    assert got["push.rounds"] == (1, int(want.it)) and int(it) == int(
        want.it)
    assert got["push.dense_rounds"] == (1, int(want.dense_rounds)) == (
        1, dense)
    assert dense >= 1 and sparse_arcs > 0  # both kinds of round ran
    assert got["push.arcs_walked"] == (
        1, g.ne * dense + sparse_arcs) == (1, push.edges_total(want.edges))
