"""Load generator: turns a traffic mix file and a seed into requests.

A mix (``bench/mixes/<name>.json``) is data only:

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last one is answered, after ``think_ms``) or
  ``"open"`` (requests due on a schedule at ``rate_per_s`` whatever the
  system does, with exponential gaps: Poisson arrivals);
- ``ops``: the operations and their ``share`` of the traffic, each with
  its parameters: ``keys`` (``{"dist": "zipf", "s": 0.99}`` or
  ``{"dist": "uniform"}``) for the vertex it reads or writes, ``values``
  (``{"low", "high"}``) for written or explicit values;
- ``stands_for``: the user workload the mix stands for, and its source.

Kinds: ``point_read`` (one aggregate of one vertex), ``whatif`` (one
aggregate of every vertex under a vector of explicit values) and
``attr_write`` (one vertex's attribute set to a new value).  Aggregates
are drawn uniformly from the configuration's.

Every seed gets the same work in another order: the same number of
operations of each kind, the same set of gaps between them, and the same
vertices read and written.  Which vertices are popular is part of the
deployment, like its graph, and does not change with the seed; the seed
draws the order, the aggregates and the values.
"""

from __future__ import annotations

import numpy as np


class Keys:
    """Vertex keys: Zipf over ranks (rank 1 the most popular) mapped to
    vertices by a fixed permutation, or uniform."""

    def __init__(self, spec: dict, n: int, rng):
        self.n = n
        self.perm = rng.permutation(n)
        if spec["dist"] == "zipf":
            w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** spec["s"]
            self.cdf = np.cumsum(w) / w.sum()
        elif spec["dist"] == "uniform":
            self.cdf = None
        else:
            raise ValueError(f"unknown key distribution {spec['dist']!r}")

    def draw(self, rng, size: int) -> np.ndarray:
        if self.cdf is None:
            return rng.integers(0, self.n, size)
        ranks = np.searchsorted(self.cdf, rng.random(size), side="right")
        return self.perm[np.minimum(ranks, self.n - 1)]


class Traffic:
    """Requests of one mix for one configuration and seed."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix = mix
        self.seed = seed
        self.n = config["graph"]["n"]
        self.n_aggs = len(config["aggregates"])
        self.kinds = [op["kind"] for op in mix["ops"]]
        # one key permutation shared by every op kind: the vertices that are
        # read most are the ones written most, as in YCSB
        self.keys = {}
        for op in mix["ops"]:
            if "keys" in op:
                self.keys[op["kind"]] = Keys(op["keys"], self.n,
                                             np.random.default_rng([0, 4]))
        self._drain_rng = np.random.default_rng([seed, 6])

    def op(self, kind: str) -> dict:
        return self.mix["ops"][self.kinds.index(kind)]

    # ----------------------------------------------------------------- #
    def open_schedule(self, seconds: float):
        """``(due_s, kind_index)`` of the operations due in a window of
        ``seconds``: round(rate * seconds) of them, each kind's count
        fixed by its share, the order and the gaps shuffled by the seed."""
        rate = float(self.mix["rate_per_s"])
        total = max(1, int(round(rate * seconds)))
        rng = np.random.default_rng([self.seed, 3])
        counts = [int(round(op["share"] * total)) for op in self.mix["ops"]]
        counts[0] += total - sum(counts)
        kinds = np.repeat(np.arange(len(counts)), counts)
        rng.shuffle(kinds)
        # exponential quantiles: the gaps of a Poisson process of this rate
        q = (np.arange(total) + 0.5) / total
        gaps = -np.log1p(-q) / rate
        gaps *= seconds / gaps.sum()
        rng.shuffle(gaps)
        due = np.cumsum(gaps) - gaps[0]
        return due, kinds

    def reads(self, count: int, rng=None):
        """``(aggregate_index, vertex)`` arrays of ``count`` point reads."""
        rng = self._drain_rng if rng is None else rng
        keys = self.keys["point_read"]
        return rng.integers(0, self.n_aggs, count), keys.draw(rng, count)

    def window_reads(self, count: int):
        """The window's ``count`` point reads: the same vertices for every
        seed, in the seed's order, each with an aggregate from the seed."""
        _, verts = self.reads(count, np.random.default_rng([0, 5]))
        rng = np.random.default_rng([self.seed, 5])
        return rng.integers(0, self.n_aggs, count), rng.permutation(verts)

    def whatif(self, i: int):
        """``(aggregate_index, values)`` of the i-th explicit-values
        request (deterministic in the seed and ``i``)."""
        vals = self.op("whatif")["values"]
        rng = np.random.default_rng([self.seed, 7, i])
        return (int(rng.integers(0, self.n_aggs)),
                rng.integers(vals["low"], vals["high"], self.n)
                .astype(np.float32))

    def write(self, j: int):
        """The j-th write, ``("attr", vertex, value)``: the same vertex for
        every seed, the value from the seed."""
        op = self.op("attr_write")
        v = int(self.keys["attr_write"].draw(np.random.default_rng([0, 8, j]),
                                             1)[0])
        rng = np.random.default_rng([self.seed, 8, j])
        return ("attr", v, int(rng.integers(op["values"]["low"],
                                            op["values"]["high"])))
