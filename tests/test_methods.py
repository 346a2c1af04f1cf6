"""Platform-aware method resolution (engine.methods): defaults follow
measured winners (PERF.md), explicit choices pass through untouched."""
import numpy as np
import pytest

from lux_tpu.engine import methods


def test_explicit_method_passes_through():
    assert methods.resolve("scatter", "sum", "tpu") == "scatter"
    assert methods.resolve("mxsum", "sum", "cpu") == "mxsum"
    assert methods.resolve("scan", "min", "cpu") == "scan"


def test_measured_winners():
    # CPU: scatter beats scan ~2x on the comp phase (round-2 CPU phase table)
    assert methods.resolve("auto", "sum", "cpu") == "scatter"
    assert methods.resolve("auto", "min", "cpu") == "scatter"
    assert methods.resolve("auto", "max", "cpu") == "scatter"
    # TPU: the scan ladder does not compile at deployment sizes (PR 21)
    assert methods.resolve("auto", "sum", "tpu") == "scatter"
    assert methods.resolve("auto", "min", "tpu") == "scatter"
    assert methods.resolve("auto", "max", "tpu") == "scatter"


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
def test_csc_auto_is_the_scan_kernel_on_tpu(reduce):
    """The csc engines' TPU default is the blocked scan kernel for every
    monoid (the chip race in ops/pallas_scan); the blanket default that
    the bucketed exchanges take stays scatter."""
    assert methods.resolve_sum("auto", reduce, "tpu") == "mxscan"
    assert methods.resolve("auto", reduce, "tpu") == "scatter"


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
def test_csc_auto_on_cpu_stays_scatter(reduce):
    assert methods.resolve_sum("auto", reduce, "cpu") == "scatter"
    # explicit choices pass through on every platform
    assert methods.resolve_sum("scan", reduce, "tpu") == "scan"
    assert methods.resolve_sum("scatter", reduce, "tpu") == "scatter"


def test_push_ring_auto_on_tpu_takes_blanket(monkeypatch):
    """The bucketed push driver with method='auto' on TPU resolves to
    the blanket scatter, never the csc kernel its assert refuses."""
    from lux_tpu.engine import push
    from lux_tpu.graph import generate
    from lux_tpu.models.sssp import SSSPProgram, bfs_reference
    from lux_tpu.parallel import mesh as mesh_lib, ring

    monkeypatch.setenv("LUX_METHOD_PLATFORM", "tpu")
    g = generate.rmat(8, 6, seed=98)
    prs = ring.build_push_ring_shards(g, 4)
    prog = SSSPProgram(nv=prs.spec.nv, start=0)
    state, _, _ = push.run_push_ring(prog, prs, mesh_lib.make_mesh(4))
    np.testing.assert_array_equal(
        prs.scatter_to_global(np.asarray(state)), bfs_reference(g, 0))


@pytest.mark.parametrize("app", ["sssp", "components"])
def test_push_app_ring_auto_on_tpu_downgrades(monkeypatch, capsys, app):
    """The push apps on --exchange ring with --method auto on TPU: the
    csc winner (mxscan, a min/max monoid here) downgrades to the blanket
    winner before the ring driver, with a note, and the run checks."""
    import importlib

    monkeypatch.setenv("LUX_METHOD_PLATFORM", "tpu")
    mod = importlib.import_module(f"lux_tpu.apps.{app}")
    res = mod.main(["--rmat-scale", "8", "-ng", "4", "--distributed",
                    "--exchange", "ring", "-check"])
    assert res == 0
    assert "csc winner mxscan downgraded to scatter" in capsys.readouterr().err


def test_unknown_platform_falls_back_portable():
    assert methods.resolve("auto", "sum", "gpu") == methods.FALLBACK


def test_resolution_is_always_concrete_and_universally_valid():
    # the winner set must stay within {scan, scatter}: cumsum/mxsum are
    # sum-only and pallas needs the block-CSR layout
    for plat in ("cpu", "tpu", "gpu", "weird"):
        for red in ("sum", "min", "max"):
            m = methods.resolve("auto", red, plat)
            assert m in methods.CONCRETE
            assert m in ("scan", "scatter")


def test_platform_env_override(monkeypatch):
    monkeypatch.setenv("LUX_METHOD_PLATFORM", "gpu")
    assert methods.resolve("auto") == methods.FALLBACK
    monkeypatch.setenv("LUX_METHOD_PLATFORM", "cpu")
    assert methods.resolve("auto") == "scatter"


def test_default_platform_detects_cpu_harness(monkeypatch):
    monkeypatch.delenv("LUX_METHOD_PLATFORM", raising=False)
    # the test harness pins JAX_PLATFORMS=cpu (conftest)
    assert methods.default_platform() == "cpu"
    assert methods.resolve("auto") == "scatter"


def test_cli_default_is_auto():
    from lux_tpu.utils.config import parse_args

    cfg = parse_args([])
    assert cfg.method == "auto"


def test_auto_runs_and_matches_resolved_concrete():
    # engine-level: method="auto" must produce bitwise the same result as
    # passing the resolved concrete method explicitly
    from lux_tpu.models import pagerank as pr
    from lux_tpu.graph import generate

    g = generate.rmat(8, 4, seed=3)
    concrete = methods.resolve("auto", "sum")
    a = pr.pagerank(g, num_iters=4, method="auto")
    b = pr.pagerank(g, num_iters=4, method=concrete)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_winners_file_overlay(monkeypatch, tmp_path, scan_tpu_rows):
    """A measured-winners file (written by the TPU bench race) overrides
    the hard-coded table; malformed entries are ignored."""
    import json

    path = tmp_path / "winners.json"
    path.write_text(json.dumps({"tpu:sum": "scatter", "tpu:min": "pallas"}))
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(path))
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
    assert methods.resolve("auto", "sum", platform="tpu") == "scatter"
    # "pallas" is not a safe blanket default: entry dropped
    assert methods.resolve("auto", "min", platform="tpu") == "scan"
    # untouched rows still come from the static table
    assert methods.resolve("auto", "sum", platform="cpu") == "scatter"
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)


def test_winners_file_malformed_is_noop(monkeypatch, tmp_path, scan_tpu_rows):
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(path))
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
    assert methods.resolve("auto", "sum", platform="tpu") == "scan"
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)


def test_winners_file_non_dict_and_sum_only_guard(monkeypatch, tmp_path, scan_tpu_rows):
    import json

    # valid JSON but not a dict: ignored, never raises
    bad = tmp_path / "list.json"
    bad.write_text(json.dumps(["tpu:sum"]))
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(bad))
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
    assert methods.resolve("auto", "sum", platform="tpu") == "scan"
    # prefix-diff strategies cannot become blanket defaults for ANY row
    # (the bucketed ring/edge2d layouts only run scan/scatter)
    mix = tmp_path / "mix.json"
    mix.write_text(json.dumps({"tpu:sum": "mxsum", "tpu:max": "scatter"}))
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(mix))
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
    assert methods.resolve("auto", "sum", platform="tpu") == "scan"
    assert methods.resolve("auto", "max", platform="tpu") == "scatter"
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)


def test_pallas_tiles_overlay(tmp_path, monkeypatch):
    """The sweep-recorded tile winner flows into build_blockcsr defaults;
    malformed/misaligned entries are ignored; explicit args always win."""
    import json

    import lux_tpu.engine.methods as methods
    from lux_tpu.graph import generate
    from lux_tpu.ops import pallas_spmv as ps

    g = generate.rmat(8, 4, seed=90)
    f = tmp_path / "w.json"
    f.write_text(json.dumps(
        {"tpu:pallas_tiles": {"v_blk": 256, "t_chunk": 1024}}
    ))
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(f))
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
    assert methods.pallas_tiles() == (256, 1024)
    bc = ps.build_blockcsr(g)
    assert (bc.v_blk, bc.t_chunk) == (256, 1024)
    # explicit args override the overlay
    bc2 = ps.build_blockcsr(g, v_blk=128, t_chunk=128)
    assert (bc2.v_blk, bc2.t_chunk) == (128, 128)
    # misaligned v_blk (not a lane multiple) is ignored
    f.write_text(json.dumps({"tpu:pallas_tiles": {"v_blk": 100,
                                                  "t_chunk": 512}}))
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
    assert methods.pallas_tiles() is None
    bc3 = ps.build_blockcsr(g)
    assert (bc3.v_blk, bc3.t_chunk) == (ps.V_BLK, ps.T_CHUNK)


def test_record_overlay_entry_survives_corrupt_file(monkeypatch, tmp_path):
    """The single overlay writer replaces a corrupt file instead of
    dropping an expensive chip measurement, honors LUX_METHOD_WINNERS,
    and round-trips through the readers."""
    import json

    f = tmp_path / "w.json"
    f.write_text("{ not json !!")
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(f))
    methods.record_overlay_entry("tpu:sum", "scatter")
    methods.record_overlay_entry(
        "tpu:pallas_tiles", {"v_blk": 128, "t_chunk": 256}
    )
    saved = json.loads(f.read_text())
    assert saved == {"tpu:sum": "scatter",
                     "tpu:pallas_tiles": {"v_blk": 128, "t_chunk": 256}}
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
    assert methods.resolve("auto", "sum", platform="tpu") == "scatter"
    assert methods.pallas_tiles() == (128, 256)


def test_record_overlay_entry_invalidates_caches(monkeypatch, tmp_path, scan_tpu_rows):
    """A process that records then reads must see its own write (ADVICE
    r4: the old writer left _overlay_raw_cache/_file_winners_cache/
    _tiles_cache stale) — no manual cache resets here on purpose."""
    f = tmp_path / "w.json"
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(f))
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
    # prime the caches with the (empty) pre-write state
    assert methods.resolve("auto", "sum", platform="tpu") == "scan"
    assert methods.pallas_tiles() is None
    methods.record_overlay_entry("tpu:sum", "scatter")
    assert methods.resolve("auto", "sum", platform="tpu") == "scatter"
    methods.record_overlay_entry(
        "tpu:pallas_tiles", {"v_blk": 256, "t_chunk": 512})
    assert methods.pallas_tiles() == (256, 512)


def test_record_overlay_merges_dict_entries(monkeypatch, tmp_path):
    """A recorded measurement must survive subsequent records — both of a
    DIFFERENT method's sub-row under the same key (the round-5 clobber:
    a later micro-race write dropped the banked mxsum/gather rows) and of
    a different key entirely (the race winner)."""
    import json

    path = tmp_path / "winners.json"
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(path))
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)

    methods.record_overlay_entry("tpu:sum", "scatter")
    methods.record_overlay_entry(
        "tpu:micro_sum", {"scale": 17, "ms_per_rep": {"mxsum": 2.0}}
    )
    # a later record for a DIFFERENT method merges, never overwrites
    methods.record_overlay_entry(
        "tpu:micro_sum", {"ms_per_rep": {"route": 0.3}}
    )
    data = json.loads(path.read_text())
    assert data["tpu:micro_sum"]["ms_per_rep"] == {
        "mxsum": 2.0, "route": 0.3
    }
    assert data["tpu:micro_sum"]["scale"] == 17
    # the race winner recorded first survived the micro-row records
    assert data["tpu:sum"] == "scatter"
    assert methods.resolve("auto", "sum", platform="tpu") == "scatter"
    # scalar re-records still overwrite (a winner is a decision)
    methods.record_overlay_entry("tpu:sum", "scan")
    assert json.loads(path.read_text())["tpu:sum"] == "scan"
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)
