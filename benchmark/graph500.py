"""The benchmark's own Graph500 Kronecker generator and `.lux` file I/O.

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

Everything is a pure function of ``seed``: the draws come from one
PCG64 stream in a fixed order (chunks of ``CHUNK`` edges, bits from the
most significant), so the same seed gives the same graph on every
machine.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

#: edges generated per inner block: small enough that the per-bit
#: temporaries stay in the CPU's cache
CHUNK = 1 << 20
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


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """The stream ``stream`` of ``seed``: the graph is stream 0, the
    traffic drawn on it stream 1.  Any integer seed is accepted."""
    return np.random.Generator(np.random.PCG64([seed % (1 << 64), stream]))


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


def dataset(cache_dir: str, name: str, params: dict, seed: int):
    """The graph of ``params`` for ``seed`` and its `.lux` path under
    ``cache_dir``.  A file made by an earlier run of the same seed is
    read instead of generated; files of other seeds are removed first,
    so the cache holds one graph per dataset."""
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, f"{name}.seed{seed}.lux")
    if os.path.exists(path):
        return read_lux(path), path, False
    for old in glob.glob(os.path.join(cache_dir, f"{name}.seed*.lux*")):
        os.remove(old)
    g = kronecker(params["scale"], params["edge_factor"], params["a"],
                  params["b"], params["c"], seed)
    write_lux(path, g)
    return g, path, True
