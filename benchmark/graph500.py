"""The benchmark's own Graph500 Kronecker generators and `.lux` file I/O.

Nothing here imports the system under test: the dataset, and every
reference answer computed from it, come from this file alone.

Generator (Graph500 specification, "Kernel 1" input): ``nv = 2**scale``
vertices, ``edge_factor * nv`` edge tuples.  Each tuple picks one
quadrant of the adjacency matrix per bit of its endpoints, with the
initiator probabilities A, B, C and D = 1 - A - B - C; one float32 draw
per edge per bit chooses the quadrant.  Vertex labels are then permuted
so that degree does not follow the label.

The graph is undirected, as Graph500's kernel 1 builds it: every tuple
is stored in both directions, so the `.lux` file holds
``2 * edge_factor * nv`` arcs and each vertex's in-edges are also its
out-edges.  Every vertex label is kept (isolated ones too), and so are
self loops and duplicate tuples, as the generator makes them: the sizes
are the same for every seed.

A configuration's ``dataset.generator`` names one of two ways to draw
that distribution; each is a pure function of ``seed``, so the same seed
gives the same graph on every machine:

- ``"graph500 kronecker"`` (``kronecker``): one process, one PCG64
  stream in a fixed order (chunks of ``CHUNK`` edges, bits from the most
  significant), then a stable sort of all arcs by destination.
- ``"graph500 kronecker chunked"`` (``kronecker_chunked``): chunks of
  ``DRAW_CHUNK`` tuples, each with a PCG64 stream of its own keyed by
  (seed, 2, chunk), the label permutation from stream (seed, 3); the
  chunks are drawn, and the arcs grouped by destination, in a pool of
  worker threads.  The graph does not depend on the number of workers,
  and its grouping is ``build_graph``'s, byte for byte.  For scales
  whose single process outlasts a run (24 and up).
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import resource
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: edges generated per inner block: small enough that the per-bit
#: temporaries stay in the CPU's cache
CHUNK = 1 << 20
#: tuples per random stream of ``kronecker_chunked`` (part of what its
#: graphs are: another size draws other graphs)
DRAW_CHUNK = 1 << 16
#: chunks in one task of ``kronecker_chunked``: the unit of work handed
#: to one worker thread
TASK_CHUNKS = 4
#: the `.lux` header: uint32 nv, uint64 ne
_HEADER = np.dtype([("nv", "<u4"), ("ne", "<u8")])


@dataclasses.dataclass(eq=False)
class Graph:
    """An undirected graph as a symmetric adjacency, host arrays only:
    ``ne`` arcs grouped by destination (``in_ptr``/``in_src``, the
    `.lux` layout), which are also the out-edges grouped by source."""

    nv: int
    ne: int
    in_ptr: np.ndarray
    in_src: np.ndarray

    @property
    def out(self):
        """(out_ptr (nv+1,) int64, out_dst (ne,) int32): each vertex's
        out-edges; the adjacency is symmetric."""
        return self.in_ptr, self.in_src

    def out_degree(self) -> np.ndarray:
        return np.diff(self.in_ptr)


def rng_for(seed: int, stream: int = 0, *sub: int) -> np.random.Generator:
    """The stream ``(stream, *sub)`` of ``seed``: ``kronecker`` draws
    from stream 0, the traffic drawn on a graph from stream 1,
    ``kronecker_chunked`` its chunk ``k`` from (2, k) and its labels
    from 3.  Any integer seed is accepted."""
    return np.random.Generator(
        np.random.PCG64([seed % (1 << 64), stream, *sub]))


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int):
    """(src, dst) int32 arrays of the Kronecker edge list, labels
    permuted."""
    if not 1 <= scale <= 30:
        raise ValueError(f"scale {scale} outside 1..30 (int32 labels)")
    nv = 1 << scale
    ne = edge_factor * nv
    rng = rng_for(seed)
    t_a, t_ab, t_abc = (np.float32(a), np.float32(a + b),
                        np.float32(a + b + c))
    src = np.empty(ne, np.int32)
    dst = np.empty(ne, np.int32)
    u = np.empty(CHUNK, np.float32)
    for lo in range(0, ne, CHUNK):
        n = min(CHUNK, ne - lo)
        uu = u[:n]
        s = src[lo:lo + n]
        d = dst[lo:lo + n]
        s[:] = 0
        d[:] = 0
        for _ in range(scale):
            rng.random(out=uu, dtype=np.float32)
            hi = uu >= t_ab
            # quadrants [0,A) (0,0), [A,A+B) (0,1), [A+B,A+B+C) (1,0),
            # [A+B+C,1) (1,1): the column bit is the parity of the three
            # threshold tests
            col = (uu >= t_a) ^ hi ^ (uu >= t_abc)
            s += s
            s += hi
            d += d
            d += col
    perm = rng.permutation(nv).astype(np.int32)
    return perm[src], perm[dst]


def _stable_order(keys: np.ndarray) -> np.ndarray:
    """Stable argsort of non-negative int32 keys, as two 16-bit radix
    passes (NumPy sorts 16-bit keys by radix when asked to be stable)."""
    lo = np.argsort((keys & 0xFFFF).astype(np.uint16), kind="stable")
    hi = np.argsort((keys[lo] >> 16).astype(np.uint16), kind="stable")
    return lo[hi]


def _grouped(keys: np.ndarray, vals: np.ndarray, nv: int):
    """(ptr (nv+1,) int64, vals grouped by key in edge order)."""
    ptr = np.zeros(nv + 1, np.int64)
    np.cumsum(np.bincount(keys, minlength=nv), out=ptr[1:])
    return ptr, vals[_stable_order(keys)]


def build_graph(src: np.ndarray, dst: np.ndarray, nv: int) -> Graph:
    """The undirected graph of the tuples ``(src[i], dst[i])``: each
    stored in both directions."""
    in_ptr, in_src = _grouped(np.concatenate([dst, src]),
                              np.concatenate([src, dst]), nv)
    return Graph(nv, 2 * len(src), in_ptr, in_src)


def kronecker(scale: int, edge_factor: int, a: float, b: float, c: float,
              seed: int) -> Graph:
    src, dst = kronecker_edges(scale, edge_factor, a, b, c, seed)
    return build_graph(src, dst, 1 << scale)


def _tasks(ne: int):
    """The tuple ranges of a phase: ``TASK_CHUNKS`` whole chunks each
    (the unit of work, not of randomness)."""
    step = DRAW_CHUNK * TASK_CHUNKS
    return [(lo, min(lo + step, ne)) for lo in range(0, ne, step)]


def _run(pool: ThreadPoolExecutor, fn, *jobs) -> list:
    """``fn`` over ``jobs`` in the pool's threads (NumPy releases the
    GIL in the fills, ufuncs, sorts and indexing they do); results in
    order, the first error raised."""
    return list(pool.map(fn, *jobs))


def chunked_tuples(pool: ThreadPoolExecutor, scale: int, edge_factor: int,
                   a: float, b: float, c: float, seed: int):
    """(src, dst) int32 arrays of the chunked generator's tuples, labels
    permuted: ``kronecker_edges``'s distribution, drawn chunk by chunk
    from streams of their own.  The label permutation is drawn while the
    pool draws the chunks."""
    if not 1 <= scale <= 30:
        raise ValueError(f"scale {scale} outside 1..30 (int32 labels)")
    nv = 1 << scale
    ne = edge_factor * nv
    src, dst = np.empty(ne, np.int32), np.empty(ne, np.int32)
    t_a, t_ab, t_abc = (np.float32(a), np.float32(a + b),
                        np.float32(a + b + c))
    shifts = np.arange(scale - 1, -1, -1, dtype=np.int32)[:, None]

    def draw(lo, hi):
        """Draw the chunks of one range, each from its own stream:
        ``kronecker_edges``'s floats and quadrants, every bit in one
        NumPy call (long calls leave the GIL free for other threads)."""
        for x in range(lo, hi, DRAW_CHUNK):
            y = min(x + DRAW_CHUNK, hi)
            u = rng_for(seed, 2, x // DRAW_CHUNK).random((scale, y - x),
                                                        np.float32)
            row = u >= t_ab
            col = (u >= t_a) ^ row ^ (u >= t_abc)
            (row << shifts).sum(axis=0, dtype=np.int32, out=src[x:y])
            (col << shifts).sum(axis=0, dtype=np.int32, out=dst[x:y])

    def relabel(lo, hi):
        for labels in (src[lo:hi], dst[lo:hi]):
            labels[:] = perm[labels]

    spans = _tasks(ne)
    drawn = pool.map(draw, *zip(*spans))
    perm = rng_for(seed, 3).permutation(nv).astype(np.int32)
    list(drawn)
    _run(pool, relabel, *zip(*spans))
    return src, dst


def _bucket_shift(nv: int) -> int:
    """log2 of the labels in one destination bucket: 256 buckets, more
    where a bucket would pass 2**16 labels (the low bits sort as
    uint16)."""
    return min(16, max(nv.bit_length() - 1 - 8, 0))


def group(pool: ThreadPoolExecutor, src: np.ndarray, dst: np.ndarray,
          nv: int) -> Graph:
    """``build_graph`` of the tuples, in the pool: the arcs are counted
    by destination bucket (the label's top bits) in each range, placed
    in order into their buckets, and each bucket is sorted stably on its
    own.  The result is ``build_graph``'s byte for byte: each
    destination's sources in the order of ``concatenate([dst, src])``."""
    ne = len(src)
    shift = _bucket_shift(nv)
    buckets, width = nv >> shift, 1 << shift
    # (destinations, sources, lo, hi) of each range of both halves
    jobs = [(k, v, lo, hi) for k, v in ((dst, src), (src, dst))
            for lo, hi in _tasks(ne)]
    counts = np.array(_run(pool, lambda k, v, lo, hi: np.bincount(
        k[lo:hi] >> shift, minlength=buckets), *zip(*jobs)), np.int64)
    ends = np.cumsum(counts.sum(axis=0))
    starts = np.cumsum(counts, axis=0) - counts + (ends - counts.sum(0))
    low = np.empty(2 * ne, np.uint16)  # destinations' low bits
    in_src = np.empty(2 * ne, np.int32)
    in_ptr = np.zeros(nv + 1, np.int64)

    def scatter(job, row_starts, row_counts):
        """Place one range's arcs, in order, at ``row_starts`` of their
        buckets."""
        keys, vals, lo, hi = job
        keys, vals = keys[lo:hi], vals[lo:hi]
        order = np.argsort((keys >> shift).astype(np.uint16), kind="stable")
        first = np.cumsum(row_counts) - row_counts
        pos = np.arange(hi - lo) + np.repeat(row_starts - first, row_counts)
        low[pos] = (keys[order] & (width - 1)).astype(np.uint16)
        in_src[pos] = vals[order]

    def sort(bucket, lo, hi):
        """Sort one bucket's arcs stably by destination and write its
        vertices' row ends."""
        order = np.argsort(low[lo:hi], kind="stable")
        in_src[lo:hi] = in_src[lo:hi][order]
        ptr = in_ptr[bucket * width + 1:(bucket + 1) * width + 1]
        np.cumsum(np.bincount(low[lo:hi], minlength=width), out=ptr)
        ptr += lo

    _run(pool, scatter, jobs, starts, counts)
    bounds = np.concatenate([[0], ends])
    _run(pool, sort, range(buckets), bounds[:-1], bounds[1:])
    return Graph(nv, 2 * ne, in_ptr, in_src)


def kronecker_chunked(scale: int, edge_factor: int, a: float, b: float,
                      c: float, seed: int,
                      workers: int | None = None) -> Graph:
    """The Kronecker graph of ``chunked_tuples``, grouped by ``group``,
    in ``workers`` threads (default ``os.cpu_count()``)."""
    with ThreadPoolExecutor(workers or os.cpu_count()) as pool:
        src, dst = chunked_tuples(pool, scale, edge_factor, a, b, c, seed)
        return group(pool, src, dst, 1 << scale)


def write_lux(path: str, g: Graph) -> None:
    """`.lux` layout: uint32 nv, uint64 ne, uint64 row ends[nv] (the end
    of each destination's in-edge block), uint32 sources[ne]."""
    tmp = path + ".part"
    with open(tmp, "wb") as f:
        np.array([(g.nv, g.ne)], _HEADER).tofile(f)
        g.in_ptr[1:].astype("<u8").tofile(f)
        g.in_src.astype("<u4").tofile(f)
    os.replace(tmp, path)


def read_lux(path: str) -> Graph:
    """The benchmark's own reader (unweighted files, written by
    ``write_lux``)."""
    with open(path, "rb") as f:
        head = np.fromfile(f, _HEADER, count=1)[0]
        nv, ne = int(head["nv"]), int(head["ne"])
        ends = np.fromfile(f, "<u8", count=nv)
        in_src = np.fromfile(f, "<u4", count=ne).view(np.int32)
    if len(in_src) != ne:
        raise ValueError(f"{path}: truncated ({len(in_src)} of {ne} edges)")
    in_ptr = np.zeros(nv + 1, np.int64)
    in_ptr[1:] = ends
    return Graph(nv, ne, in_ptr, in_src)


#: ``dataset.generator`` names and the generators they select
GENERATORS = {"graph500 kronecker": kronecker,
              "graph500 kronecker chunked": kronecker_chunked}


def dataset(cache_dir: str, name: str, params: dict, seed: int):
    """The graph of ``params`` for ``seed`` and its `.lux` path under
    ``cache_dir``, made by the generator ``params["generator"]`` names.
    A file made by an earlier run of the same seed is read instead of
    generated; files of other seeds are removed first, so the cache
    holds one graph per dataset."""
    make = GENERATORS.get(params["generator"])
    if make is None:
        raise ValueError(f"unknown dataset generator {params['generator']!r}"
                         f"; known: {sorted(GENERATORS)}")
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, f"{name}.seed{seed}.lux")
    if os.path.exists(path):
        return read_lux(path), path, False
    for old in glob.glob(os.path.join(cache_dir, f"{name}.seed*.lux*")):
        os.remove(old)
    g = make(params["scale"], params["edge_factor"], params["a"],
             params["b"], params["c"], seed)
    write_lux(path, g)
    return g, path, True


def maxrss_gb() -> float:
    """Peak resident memory of this process, GB."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def main(argv=None) -> int:
    """Time one generator's phases: draw, group by destination, write
    the `.lux` file (removed afterwards).  One JSON line: the seconds of
    each phase, the worker threads and the peak resident memory."""
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--scale", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--generator", required=True, choices=sorted(GENERATORS))
    ap.add_argument("--out", required=True, help="directory of the file")
    ap.add_argument("--workers", type=int, help="default: os.cpu_count()")
    args = ap.parse_args(argv)
    params = (args.scale, 16, 0.57, 0.19, 0.19, args.seed)
    nv = 1 << args.scale
    t = [time.perf_counter()]
    if GENERATORS[args.generator] is kronecker:
        workers = 1
        src, dst = kronecker_edges(*params)
        t.append(time.perf_counter())
        g = build_graph(src, dst, nv)
    else:
        workers = args.workers or os.cpu_count()
        with ThreadPoolExecutor(workers) as pool:
            src, dst = chunked_tuples(pool, *params)
            t.append(time.perf_counter())
            g = group(pool, src, dst, nv)
    del src, dst
    t.append(time.perf_counter())
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"graph500-s{args.scale}.seed{args.seed}"
                                  ".lux")
    write_lux(path, g)
    t.append(time.perf_counter())
    os.remove(path)
    print(json.dumps({
        "generator": args.generator, "scale": args.scale, "seed": args.seed,
        "nv": g.nv, "ne": g.ne, "cpu_count": os.cpu_count(),
        "workers": workers, "generation_s": t[1] - t[0],
        "grouping_s": t[2] - t[1], "write_s": t[3] - t[2],
        "total_s": t[3] - t[0], "maxrss_gb": maxrss_gb()}),
        flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
