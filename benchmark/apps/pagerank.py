"""PageRank: Lux PageRank through the calls of ``apps/pagerank.main`` on
its one-part (``-ng 1``) path with default flags, and the benchmark's
own float64 reference.

Semantics (Lux ``pagerank/app.h`` and ``pagerank_gpu.cu``): ranks are
stored divided by out-degree (undivided where the degree is 0), start at
1/nv, and one iteration sets
``new[v] = (1 - ALPHA)/nv + ALPHA * sum over in-edges u->v of old[u]``,
then divides by deg[v].  A job is ``iterations`` such iterations from
the start vector, ending with the ranks back on the host.
"""
from __future__ import annotations

import numpy as np

#: the state widths the configuration states: int32 indices, f32 ranks
INDEX_BYTES = 4
VALUE_BYTES = 4


def prepare(conf: dict, graph) -> dict:
    """Dataset-phase host work: none beyond the graph; the reference
    ranks are kept here once computed."""
    return {"eligible": None, "ranks": None}


def setup(conf: dict, lux_path: str, span, control=None) -> dict:
    """Load the `.lux` file, build and place the shards, as the app does.
    A ``control`` (any non-None value) switches the program's own
    bfloat16 state path on."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.apps import common
    from lux_tpu.models.pagerank import PageRankProgram
    from lux_tpu.utils.config import parse_args

    argv = ["-file", lux_path, "-ni", str(conf["semantics"]["iterations"]),
            *conf.get("flags", [])]
    if control is not None:
        argv += ["--dtype", "bfloat16"]
    with span("graph.load"):
        cfg = parse_args(argv, pull=True, stream=True, serve=True)
        g = common.load_graph(cfg)
        prog = PageRankProgram(nv=g.nv, dtype=cfg.dtype)
        common.validate_exchange(cfg, prog)
        shards = common.build_exchange_shards(g, cfg)
        arrays = jax.block_until_ready(
            jax.tree.map(jnp.asarray, shards.arrays))
        route = common.build_pull_route(cfg, shards, prog)
    return {"cfg": cfg, "prog": prog, "shards": shards, "arrays": arrays,
            "route": route}


def job(st: dict, source, span) -> np.ndarray:
    """One PageRank job; returns the (nv,) ranks on the host."""
    from lux_tpu.engine import pull

    with span("job.init"):
        s0 = pull.init_state(st["prog"], st["arrays"])
    with span("job.run"):
        out = pull.run_pull_fixed(st["prog"], st["shards"].spec, st["arrays"],
                                  s0, st["cfg"].num_iters, st["cfg"].method,
                                  route=st["route"])
        out.block_until_ready()
    with span("job.fetch"):
        return st["shards"].scatter_to_global(np.asarray(out))


def work(conf: dict, graph, prep: dict, source) -> tuple[int, int]:
    """(useful edges, useful bytes) of one job, from the graph's sizes
    alone: per iteration every arc (each undirected edge carries a rank
    both ways) with its source index and source value, and every
    vertex's offset, degree, old and new value, once."""
    it = conf["semantics"]["iterations"]
    per_iter = (graph.ne * (INDEX_BYTES + VALUE_BYTES)
                + graph.nv * (2 * INDEX_BYTES + 2 * VALUE_BYTES))
    return it * graph.ne, it * per_iter


def reference(conf: dict, graph, source) -> np.ndarray:
    """The recurrence in float64: each iteration one sparse product with
    the adjacency (SciPy's compiled loop; duplicate arcs count once
    each)."""
    import scipy.sparse as sp

    sem = conf["semantics"]
    alpha, nv = sem["alpha"], graph.nv
    deg = graph.out_degree().astype(np.float64)
    div = np.where(deg > 0, deg, 1.0)
    ptr = graph.in_ptr.astype(np.int32 if graph.ne < 2**31 else np.int64)
    adj = sp.csr_matrix((np.ones(graph.ne), graph.in_src, ptr),
                        shape=(nv, nv))
    x = np.full(nv, 1.0 / nv) / div
    for _ in range(sem["iterations"]):
        x = ((1.0 - alpha) / nv + alpha * (adj @ x)) / div
    return x


def check(conf: dict, graph, prep: dict, source, answer) -> dict:
    """The numbers compared for one job.  ``rank_err``: the largest
    relative error of any vertex's rank against the reference (every
    rank is positive)."""
    if prep["ranks"] is None:
        prep["ranks"] = reference(conf, graph, source)
    want = prep["ranks"]
    err = np.abs(np.asarray(answer, np.float64) - want) / want
    return {"rank_err": float(np.max(err))
            if np.all(np.isfinite(err)) else float("inf")}
