"""Plain reference for the benchmark's window aggregates.

Windows come from breadth-first search over the edge lists: a k-hop window
holds every vertex within ``k`` hops along out-edges (both ways on an
undirected graph), a topological window every ancestor of the vertex and
the vertex itself.  Aggregates are one NumPy reduce over each window's
member values.  Nothing here imports the program under test.

``dtype`` is the precision of the reduce.  The configurations state
float32 on integer values in [0, 100), where every partial sum is an exact
integer, so the reference is exact in any order and a served answer must
equal it bit for bit.  The control runs the same reduce in bfloat16.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

AGGREGATES = ("sum", "count", "avg", "min", "max")
DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}


def _csr(n: int, src: np.ndarray, dst: np.ndarray):
    """``(indptr, indices)`` of the rows ``src`` -> columns ``dst``."""
    order = np.argsort(src, kind="stable")
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst[order].astype(np.int64)


class RefGraph:
    """Edge lists with the adjacency the searches need."""

    def __init__(self, n: int, src, dst, directed: bool):
        self.n = int(n)
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.directed = bool(directed)
        if directed:
            s, d = self.src, self.dst
        else:
            s = np.concatenate([self.src, self.dst])
            d = np.concatenate([self.dst, self.src])
        self.out = _csr(self.n, s, d)
        self.inn = _csr(self.n, d, s)


def _expand(adj, frontier: np.ndarray) -> np.ndarray:
    """All neighbours of ``frontier`` under the CSR ``adj``."""
    indptr, indices = adj
    starts = indptr[frontier]
    lens = indptr[frontier + 1] - starts
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, np.int64)
    offs = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
    return indices[np.repeat(starts, lens) + offs]


def _bfs(adj, n: int, v: int, hops=None) -> np.ndarray:
    """Sorted vertices reached from ``v`` within ``hops`` steps (no limit
    when None), ``v`` included."""
    seen = np.zeros(n, bool)
    seen[v] = True
    frontier = np.array([v], np.int64)
    step = 0
    while frontier.size and (hops is None or step < hops):
        nbr = _expand(adj, frontier)
        nbr = np.unique(nbr[~seen[nbr]])
        seen[nbr] = True
        frontier = nbr
        step += 1
    return np.flatnonzero(seen)


def khop_windows(g: RefGraph, k: int, vertices=None):
    """CSR ``(indptr, members)`` of the k-hop windows of ``vertices`` (all
    when None).  All windows are searched at once, one hop at a time: the
    reached set grows by its neighbours, as a sparse boolean product."""
    vs = np.arange(g.n) if vertices is None else np.asarray(vertices)
    indptr, indices = g.out
    adj = sp.csr_matrix((np.ones(indices.size, np.int32), indices, indptr),
                        shape=(g.n, g.n))
    reach = sp.csr_matrix((np.ones(vs.size, np.int32),
                           (np.arange(vs.size), vs)), shape=(vs.size, g.n))
    for _ in range(k):
        reach = reach + reach @ adj
        reach.data[:] = 1
    reach.sort_indices()
    return reach.indptr.astype(np.int64), reach.indices.astype(np.int64)


def ancestor_windows(g: RefGraph, vertices):
    """CSR ``(indptr, members)`` of the topological windows of
    ``vertices``: a reverse search over in-edges from each."""
    wins = [_bfs(g.inn, g.n, int(v)) for v in vertices]
    indptr = np.zeros(len(wins) + 1, np.int64)
    np.cumsum([w.size for w in wins], out=indptr[1:])
    members = np.concatenate(wins) if wins else np.empty(0, np.int64)
    return indptr, members


def windows(g: RefGraph, window: dict, vertices=None):
    """Windows of a configuration's ``window`` entry."""
    if window["kind"] == "khop":
        return khop_windows(g, window["k"], vertices)
    if window["kind"] == "topological":
        vs = np.arange(g.n) if vertices is None else vertices
        return ancestor_windows(g, vs)
    raise ValueError(f"unknown window kind {window['kind']!r}")


def reduce(values, indptr, members, agg: str, dtype=np.float32):
    """``agg`` of ``values`` over every window of the CSR, reduced in
    ``dtype`` and returned as float32.  Windows are never empty: each holds
    its own vertex."""
    sizes = np.diff(indptr)
    if sizes.size and sizes.min() < 1:
        raise ValueError("an empty window")
    starts = indptr[:-1]
    if agg in ("min", "max"):
        ufunc = np.minimum if agg == "min" else np.maximum
        out = ufunc.reduceat(np.asarray(values)[members].astype(dtype), starts)
    else:
        count = sizes.astype(dtype)
        total = np.add.reduceat(np.asarray(values)[members].astype(dtype),
                                starts) if agg != "count" else None
        out = {"sum": total, "count": count,
               "avg": None if total is None else total / count}[agg]
    return np.asarray(out).astype(np.float32)
