"""BFS: unweighted hop levels through the calls of ``apps/sssp.main`` on
its single-device path with default flags (``engine.push.run_push`` with
``SSSPProgram(nv, start)``), and the benchmark's own NumPy BFS.

Semantics (Graph500 kernel 2, LDBC Graphalytics BFS): on the undirected
graph, the level of a vertex is its hop count from the root; a vertex
the root does not reach holds ``nv``.  Roots have degree 1 or more
(Graph500's rule) and are offered highest degree first.

The push shards are placed on the device once, in set-up, as for a
deployment that keeps the graph resident; ``run_push``'s own
``jnp.asarray`` of them then moves nothing.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: the state widths the configuration states: int32 indices and levels
INDEX_BYTES = 4
LEVEL_BYTES = 4


def levels(out_ptr, out_dst, nv: int, root: int, max_depth=None):
    """Level-synchronous BFS over the out-edge form; ``nv`` = unreached.
    ``max_depth`` stops after that many levels (the control)."""
    lev = np.full(nv, nv, np.int32)
    lev[root] = 0
    frontier = np.array([root], np.int64)
    depth = 0
    while frontier.size and (max_depth is None or depth < max_depth):
        depth += 1
        starts = out_ptr[frontier]
        counts = out_ptr[frontier + 1] - starts
        total = int(counts.sum())
        if total == 0:
            break
        first = np.repeat(starts - np.cumsum(counts) + counts, counts)
        nbrs = out_dst[first + np.arange(total)]
        nbrs = nbrs[lev[nbrs] == nv]
        lev[nbrs] = depth
        frontier = np.flatnonzero(np.bincount(nbrs, minlength=nv))
    return lev


def prepare(conf: dict, graph) -> dict:
    """Dataset-phase host work: the roots a mix may take, every vertex
    of degree 1 or more, highest degree first (ties by id); the
    reference levels of each root used are kept here (``levels``)."""
    deg = graph.out_degree()
    order = np.argsort(-deg, kind="stable")
    return {"eligible": order[:np.count_nonzero(deg)], "levels": {}}


def reference(graph, prep: dict, root: int) -> np.ndarray:
    if root not in prep["levels"]:
        prep["levels"][root] = levels(*graph.out, graph.nv, root)
    return prep["levels"][root]


def setup(conf: dict, lux_path: str, span, control=None):
    """Load the `.lux` file, build the push shards as the app does and
    place them.  ``control`` (the benchmark's graph) puts the reference
    in the program's place, one level short: every vertex of the
    deepest level is left unreached."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.apps import common
    from lux_tpu.apps.sssp import build_push_app_shards
    from lux_tpu.engine import methods
    from lux_tpu.graph.push_shards import PushShards
    from lux_tpu.models.sssp import SSSPProgram
    from lux_tpu.utils.config import parse_args

    with span("graph.load"):
        cfg = parse_args(["-file", lux_path, *conf.get("flags", [])],
                         sssp=True, push=True, serve=True)
        g = common.load_graph(cfg)
        shards = build_push_app_shards(g, cfg)
        cfg.method = methods.resolve_sum(
            cfg.method, SSSPProgram(nv=g.nv).reduce)
        put = jax.tree.map(jnp.asarray, (shards.pull.arrays, shards.parrays))
        jax.block_until_ready(put)
        dev = PushShards(pull=dataclasses.replace(shards.pull, arrays=put[0]),
                         pspec=shards.pspec, parrays=put[1])
    return {"cfg": cfg, "nv": g.nv, "shards": dev,
            "control": control}


def job(st: dict, root: int, span) -> np.ndarray:
    """One traversal from ``root``; returns the (nv,) levels on the
    host."""
    from lux_tpu.engine import push
    from lux_tpu.models.sssp import SSSPProgram

    if st["control"] is not None:
        g = st["control"]
        full = levels(*g.out, g.nv, root)
        return levels(*g.out, g.nv, root, max_depth=int(
            full[full < g.nv].max()) - 1)
    with span("root.prepare"):
        prog = SSSPProgram(nv=st["nv"], start=root)
    with span("job.run"):
        state, _, _ = push.run_push(prog, st["shards"], st["cfg"].max_iters,
                                    st["cfg"].method)
        state.block_until_ready()
    with span("job.fetch"):
        return st["shards"].scatter_to_global(np.asarray(state))


def work(conf: dict, graph, prep: dict, root) -> tuple[int, int]:
    """(useful edges, useful bytes) of one traversal, from the reference
    answer.  Edges: Graph500's count, the input tuples inside the
    root's component (half the arcs of the vertices reached).  Bytes:
    each reached vertex's offset and arc indices, each such arc's
    destination level, and every vertex's level write, once."""
    reached = reference(graph, prep, root) < graph.nv
    r = int(np.count_nonzero(reached))
    arcs = int(graph.out_degree()[reached].sum())
    return arcs // 2, (r * INDEX_BYTES + arcs * (INDEX_BYTES + LEVEL_BYTES)
                       + graph.nv * LEVEL_BYTES)


def check(conf: dict, graph, prep: dict, root, answer) -> dict:
    """``level_mismatches``: vertices whose level differs from the
    reference's."""
    return {"level_mismatches": int(np.count_nonzero(
        np.asarray(answer) != reference(graph, prep, root)))}
