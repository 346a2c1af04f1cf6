"""The whole run of each cell on the CPU at a tiny scale: the rehearsal,
the refusal without a TPU, the control, and the faults that the
comparison must catch.  No number here is a device number."""
import json

import jax
import numpy as np
import pytest

from benchmark import harness

CELLS = ["pr-graph500-22.back_to_back", "bfs-graph500-22.one_root"]
SCALE = "9"


def _run(capsys, cell, *extra, seed=2**31 + 11):
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "0.5", "--cpu-rehearsal", SCALE, *extra])
    out = capsys.readouterr()
    return rc, out


def _result(out):
    return json.loads(out.out.strip().splitlines()[-1])


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Faults are planted in module functions that compiled programs
    close over: start and end every test from empty program caches."""
    from lux_tpu.engine import push

    jax.clear_caches()
    push._compile_push_chunk_cached.cache_clear()
    yield
    jax.clear_caches()
    push._compile_push_chunk_cached.cache_clear()


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_marked(capsys, cell, trace):
    rc, out = _run(capsys, cell, "--trace", trace)
    assert rc == 0
    res = _result(out)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "rehearsal"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"] == {}
    assert res["rehearsal"]["note"].startswith("CPU run")
    names = set(res["rehearsal"]["metrics"])
    want = ({"graph.load_s", "compile.setup_s", "compile.window_s"}
            if trace == "1" else {"gteps", "setup_s"})
    assert names == want
    assert res["device"]["platform"] == "cpu"
    for k, c in res["checks"].items():
        assert f"check {k} " in out.err.strip().splitlines()[-1]


def test_readings(capsys):
    """The readings mode prints each seed's numbers and its control's."""
    rc = harness.main(["--workload", ",".join(CELLS), "--seed", "0",
                       "--seconds", "0", "--cpu-rehearsal", SCALE,
                       "--readings", "3,4"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    got = [(x["workload"], x["seed"], x["control"], x["correct"])
           for x in lines if x.get("phase") == "reading"]
    assert rc == 0 and got == [(c, s, k, not k) for s in (3, 4)
                               for c in CELLS for k in (False, True)]


@pytest.mark.parametrize("cell", CELLS)
def test_no_tpu_no_result(capsys, cell):
    rc = harness.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                       "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 3
    assert out.out == ""
    assert "no TPU" in out.err


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(capsys, cell):
    rc, out = _run(capsys, cell, "--control")
    res = _result(out)
    assert rc == 0 and res["correct"] is False and res["failed"] >= 1


def _unchanged_pull(prog, spec, method, arrays, state, *a, **k):
    return state


def _unchanged_push(prog, pspec, spec, method, arrays, parrays, c, *a, **k):
    return c.state


def _altered(run):
    def wrapped(*a, **k):
        out = run(*a, **k)
        first = out if not isinstance(out, tuple) else out[0]
        step = (1 if np.issubdtype(first.dtype, np.integer)
                else first[0, 1] * 0.01)
        first = first.at[0, 1].add(step)
        return first if not isinstance(out, tuple) else (first, *out[1:])
    return wrapped


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_faults_are_caught(capsys, monkeypatch, cell, fault):
    from lux_tpu.engine import pull, push

    if fault == "state_unchanged":
        if cell.startswith("pr"):
            monkeypatch.setattr(pull, "_pull_iteration", _unchanged_pull)
        else:
            monkeypatch.setattr(push, "_push_relax", _unchanged_push)
    elif cell.startswith("pr"):
        monkeypatch.setattr(pull, "run_pull_fixed",
                            _altered(pull.run_pull_fixed))
    else:
        monkeypatch.setattr(push, "run_push", _altered(push.run_push))
    rc, out = _run(capsys, cell)
    res = _result(out)
    assert rc == 0 and res["correct"] is False
    assert res["failed"] == res["attempted"]


@pytest.mark.parametrize("flags", [["--method", "scatter"],
                                   ["--method", "mxscan"],
                                   ["--route-gather", "expand-pf"]])
def test_floor_is_the_same_for_every_method(tmp_path, flags):
    """The useful bytes of a job come from the graph alone: each method
    and a routed plan give a correct answer under the same floor."""
    from benchmark import graph500
    from benchmark.apps import pagerank

    conf = harness._json(f"{harness.BENCH_DIR}/configs/pr-graph500-22.json")
    g = graph500.kronecker(8, 16, 0.57, 0.19, 0.19, 21)
    path = str(tmp_path / "g.lux")
    graph500.write_lux(path, g)
    spans = harness.Spans()
    floors, answers = [], []
    for f in ([], flags):
        st = pagerank.setup({**conf, "flags": f}, path, spans)
        answers.append(pagerank.job(st, None, spans))
        floors.append(pagerank.work(conf, g, {}, None))
    assert floors[0] == floors[1]
    for ans in answers:
        got = pagerank.check(conf, g, pagerank.prepare(conf, g), None, ans)
        assert got["rank_err"] <= conf["limits"]["rank_err"]
    np.testing.assert_allclose(answers[0], answers[1], rtol=1e-5)
