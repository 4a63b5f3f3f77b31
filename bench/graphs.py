"""Graph data for the benchmark's configurations.

The configuration file names the generator and its parameters under
``graph``.  Each generator returns ``(src, dst)`` int32 edge arrays.  The
graph is one fixed draw (generator seed 0) for every run of a
configuration, as a dataset is: ``--seed`` draws the attribute values and
the traffic, never the graph, so every seed serves the same index and
plan shapes.
"""

from __future__ import annotations

import numpy as np


def _dedupe(src: np.ndarray, dst: np.ndarray, n: int):
    """Drop self-loops and duplicate edges, keeping first occurrences."""
    keep = src != dst
    src, dst = src[keep], dst[keep]
    _, idx = np.unique(src.astype(np.int64) * n + dst, return_index=True)
    idx = np.sort(idx)
    return src[idx], dst[idx]


def rmat(rng, n: int, avg_degree: float, initiator, directed: bool = False):
    """Kronecker (R-MAT) graph as the Graph500 specification generates it:
    each edge picks one quadrant of the adjacency matrix per level with the
    ``initiator`` probabilities (a, b, c, d), and the vertex labels are
    permuted at random.  ``n`` is a power of two.  Self-loops and duplicate
    edges are dropped (an undirected edge once, either way round) and edges
    are drawn until ``n * avg_degree`` ends of edges remain (half as many
    edges when undirected)."""
    scale = int(n).bit_length() - 1
    if n != 1 << scale:
        raise ValueError(f"R-MAT needs a power of two, not n={n}")
    a, b, c, _ = initiator
    m = int(round(n * avg_degree / (1 if directed else 2)))
    src = np.empty(0, np.int64)
    dst = np.empty(0, np.int64)
    while src.size < m:
        draw = int((m - src.size) * 1.5) + 1024
        s = np.zeros(draw, np.int64)
        t = np.zeros(draw, np.int64)
        for _ in range(scale):
            r = rng.random(draw)
            s = 2 * s + (r >= a + b)
            t = 2 * t + (((r >= a) & (r < a + b)) | (r >= a + b + c))
        if not directed:
            s, t = np.minimum(s, t), np.maximum(s, t)
        src, dst = _dedupe(np.concatenate([src, s]), np.concatenate([dst, t]),
                           n)
    perm = rng.permutation(n)
    return perm[src[:m]].astype(np.int32), perm[dst[:m]].astype(np.int32)


def price_dag(rng, n: int, citations: float, offset: int = 1,
              directed: bool = True):
    """Citation DAG by Price's cumulative-advantage model: papers arrive
    one at a time, and each cites ``citations`` earlier papers on average
    (the integer part, one more with the fractional part's probability),
    each chosen with probability proportional to its citations so far plus
    ``offset``.  An edge u -> v is "u cites v".  Paper labels are permuted
    at random."""
    if not directed:
        raise ValueError("a citation DAG is directed")
    whole = int(np.floor(citations))
    refs = whole + (rng.random(n) < citations - whole)
    # one entry per paper for the offset and one per citation received:
    # a uniform draw from the pool picks a paper with the wanted weight
    pool = np.empty(n * offset + int(refs.sum()), np.int64)
    size = 0
    src, dst = [], []
    for i in range(n):
        k = min(int(refs[i]), i)
        if k:
            cited: set = set()
            while len(cited) < k:
                cited.update(pool[rng.integers(0, size, k - len(cited))]
                             .tolist())
            chosen = np.fromiter(cited, np.int64, len(cited))[:k]
            src.append(np.full(k, i, np.int64))
            dst.append(chosen)
            pool[size:size + k] = chosen
            size += k
        pool[size:size + offset] = i
        size += offset
    perm = rng.permutation(n)
    return (perm[np.concatenate(src)].astype(np.int32),
            perm[np.concatenate(dst)].astype(np.int32))


GENERATORS = {"rmat": rmat, "price_dag": price_dag}


def make_graph(spec: dict):
    """``(src, dst)`` of a configuration's ``graph`` entry: the generator's
    name under ``generator``, its parameters beside it."""
    params = {k: v for k, v in spec.items() if k != "generator"}
    return GENERATORS[spec["generator"]](np.random.default_rng(0), **params)


def make_attribute(spec: dict, n: int, seed: int) -> np.ndarray:
    """The served attribute: integers in ``[low, high)`` from the seed, as
    float64 (the program's attribute dtype)."""
    rng = np.random.default_rng([seed, 2])
    return rng.integers(spec["low"], spec["high"], size=n).astype(np.float64)
