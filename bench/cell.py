"""One run of one benchmark cell: set-up, the measured window, the check.

The program is reached only through ``Session(device=True)`` and
``AsyncWindowService`` (no write-ahead log, ``auto_flip``, the default
request classes).  Reads are tickets submitted to the service; writes go
through ``service.update``.  Everything else here (data, traffic, the
reference, the arithmetic of the metrics) belongs to the benchmark.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import queue
import sys
import threading
import time
from collections import defaultdict
from pathlib import Path
from typing import Optional

import numpy as np

from bench import graphs, oracle, roofline
from bench import trace as xtrace
from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parent.parent
BENCH_DIR = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DRAIN_MAX_S = 30.0  # reads after the window that wait for the last writes
ANSWER_WAIT_S = 90.0  # how long an answer due in the window is waited for


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------- #
#  BENCHMARK.json and the files it names
# ---------------------------------------------------------------------- #
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def find(cls, name: str, root: Path = ROOT) -> "Cell":
        """The workload ``name`` of ``BENCHMARK.json``."""
        bench = load_json(root / "BENCHMARK.json")
        workloads = {w["name"]: w for w in bench["workloads"]}
        if name not in workloads:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"have {sorted(workloads)}")
        return cls.of(bench, workloads[name], root)

    @classmethod
    def of(cls, bench: dict, workload: dict, root: Path = ROOT) -> "Cell":
        """A cell from its entry: the configuration and mix files it
        names, and the metrics that apply to it."""
        name = workload["name"]
        entry = {c["name"]: c for c in bench["configs"]}[workload["config"]]
        config = load_json(root / entry["file"])
        mix = load_json(BENCH_DIR / "mixes" / f"{workload['traffic']}.json")

        def applies(m):
            return name in m.get("workloads", [name])

        return cls(workload, config, mix,
                   [m for m in bench["end_to_end"] if applies(m)],
                   [m for m in bench["per_layer"] if applies(m)])


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``, or of
    the reader of the name's first part (``idle_share.read`` falls back to
    ``idle_share.py``) where the metric has no reader of its own."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH_DIR / "metrics" / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------- #
#  Records of the traffic
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class Read:
    due: float
    submitted: float
    ticket: Optional[object]
    agg: int
    vertex: Optional[int] = None  # None: an explicit-values request
    index: int = -1  # explicit-values request number (its values' seed)
    in_window: bool = True
    error: Optional[BaseException] = None

    @property
    def ok(self) -> bool:
        t = self.ticket
        return t is not None and t.done and not t.failed

    @property
    def completed(self) -> float:
        return self.ticket.submitted_s + self.ticket.latency_s


@dataclasses.dataclass
class Write:
    due: float
    op: tuple
    in_window: bool = True
    acked: float = float("nan")
    version: int = -1
    error: Optional[BaseException] = None


def quantile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation."""
    v = np.asarray(values, np.float64)
    return float(np.quantile(v, q)) if v.size else float("nan")


# ---------------------------------------------------------------------- #
class Run:
    """One run of a cell.  ``n`` shrinks the configuration's graph (tests
    on the CPU); ``control`` names the precision of the control, whose
    reference answers replace the served ones in the comparison."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 *, t_start: float, n: Optional[int] = None,
                 control: Optional[str] = None, compile_cache: bool = True,
                 trace_dir: Optional[Path] = None):
        if seed < 0:
            raise ValueError("seeds are non-negative")
        self.cell = cell
        self.config = json.loads(json.dumps(cell.config))
        if n is not None:
            self.config["graph"]["n"] = n
        self.mix = cell.mix
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.control = control
        self.compile_cache = compile_cache
        self.trace_dir = trace_dir or ROOT / "bench" / "_out" / "trace"
        self.traffic = Traffic(self.mix, self.config, seed)
        self.reads: list = []
        self.writes: list = []  # every applied write, in version order
        self.lateness: list = []
        self.compiles = 0  # backend compiles inside the window
        self.n_writes = 0  # writes handed to the writer, warm-up included
        self.notes: dict = {}

    # ------------------------------------------------------------------ #
    #  Set-up
    # ------------------------------------------------------------------ #
    def setup(self) -> None:
        import jax

        if self.compile_cache:
            import os

            # every compile of the set-up goes to the cache at a fixed path
            # in the checkout, so that later runs of the cell load them
            jax.config.update(
                "jax_compilation_cache_dir",
                os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or str(ROOT / ".jax_cache"))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

        from repro.core.api import QuerySpec, Session
        from repro.core.graph import Graph
        from repro.obs.tracing import Tracer
        from repro.serve import AsyncWindowService

        cfg = self.config
        src, dst = graphs.make_graph(cfg["graph"])
        attr = cfg["attribute"]
        self.values0 = graphs.make_attribute(attr, cfg["graph"]["n"],
                                             self.seed)
        self.ref_graph = oracle.RefGraph(cfg["graph"]["n"], src, dst,
                                         cfg["graph"]["directed"])
        g = Graph(n=cfg["graph"]["n"], src=src, dst=dst,
                  directed=cfg["graph"]["directed"])
        g = g.with_attr(attr["name"], self.values0)
        win = cfg["window"]
        window = ("khop", win["k"]) if win["kind"] == "khop" else win["kind"]
        specs = [QuerySpec(window, a, attr=attr["name"], engine=cfg["engine"])
                 for a in cfg["aggregates"]]
        self.tracer = Tracer(capacity=1 << 20)
        t_pc = time.perf_counter()
        self.tracer.instant("bench.clock")
        # the tracer's clock starts at its own epoch: find it once
        self.tracer_epoch = t_pc - self.tracer.events()[-1]["ts"] / 1e6
        with jax.profiler.TraceAnnotation("bench.setup"):
            self.session = Session(g, specs, device=True, tracer=self.tracer)
            svc = cfg["service"]
            self.service = AsyncWindowService(
                self.session, bucket=svc["bucket"],
                auto_flip=svc["auto_flip"], tracer=self.tracer)
            self.service.start()
            self._warm_up()
        self.counts = roofline.plan_counts(
            self.session.snapshot().artifacts[0][0][0])
        self.bucket = self.service.bucket
        if self.compile_cache:
            # compiles the traffic forces inside the window depend on the
            # seed's data; keep them out of the cache so that every run of
            # a seed does the same work
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              1e9)
        self.setup_s = time.perf_counter() - self.t_start

    def _on_compile(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    @contextlib.contextmanager
    def _counting_compiles(self):
        """Count the backend compiles of the window (there should be none)
        and stamp when the run stopped waiting for answers."""
        import jax

        from repro.core.api import recompile_count

        c0 = recompile_count()
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        try:
            yield
        finally:
            jax.monitoring.unregister_event_duration_listener(
                self._on_compile)
            self.t_giveup = time.perf_counter()
            self.notes["executor_retraces"] = recompile_count() - c0

    def _warm_up(self) -> None:
        """Run each shape the cell's traffic uses once: a full bucket of
        explicit-values requests; a point read (a whole-group refresh); two
        writes, each followed by a read at its version."""
        kinds = self.traffic.kinds
        if "whatif" in kinds:
            tickets = [self.service.submit(agg, values=vals) for agg, vals in
                       map(self.traffic.whatif, range(self.service.bucket))]
            for t in tickets:
                t.get(timeout=600)
            self.next_whatif = self.service.bucket
        if "point_read" in kinds:
            self.service.submit(0, vertex=0).get(timeout=600)
            for _ in range(2 if "attr_write" in kinds else 0):
                self._apply(self._next_write(time.perf_counter(), False))
                self.service.submit(0, vertex=0).get(timeout=600)
            self.service.submit(1, vertex=0).get(timeout=600)

    def _next_write(self, due: float, in_window: bool = True) -> Write:
        j = self.n_writes
        self.n_writes += 1
        return Write(due, self.traffic.write(j), in_window)

    def _apply(self, w: Write) -> None:
        """Apply one write through the service and record it."""
        import jax

        from repro.core.updates import UpdateBatch

        _, vertex, value = w.op
        batch = UpdateBatch.attr_set(self.config["attribute"]["name"],
                                     [vertex], [float(value)])
        try:
            with jax.profiler.TraceAnnotation("bench.update"):
                reports = self.service.update(batch)
            w.version = max(r["version"] for r in reports.values())
        except Exception as e:  # recorded and counted against `correct`
            w.error = e
        w.acked = time.perf_counter()
        self.writes.append(w)

    # ------------------------------------------------------------------ #
    #  The measured window
    # ------------------------------------------------------------------ #
    def run(self) -> None:
        import jax

        self.stats0 = self.service.stats
        if self.trace:
            import shutil

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.trace_dir))
        try:
            if self.mix["loop"] == "closed":
                self._closed_loop()
            else:
                self._open_loop()
        finally:
            if self.trace:
                jax.profiler.stop_trace()
        self.service.stop(drain=True)
        dev = jax.local_devices()[0]
        stats = dev.memory_stats() or {}
        self.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
        # the reference runs on the host once the program's state is gone
        self.service = self.session = None

    def _closed_loop(self) -> None:
        import jax

        clients = int(self.mix["clients"])
        slots: list = [None] * clients
        i = self.next_whatif
        self.t0 = time.perf_counter()
        stop_at = self.cut = self.t0 + self.seconds
        with self._counting_compiles(), \
                jax.profiler.TraceAnnotation("bench.window"):
            while True:
                now = time.perf_counter()
                busy = False
                for c in range(clients):
                    r = slots[c]
                    if r is not None and r.ticket is not None \
                            and not r.ticket.done:
                        busy = True
                        continue
                    slots[c] = None
                    if now < stop_at:
                        agg, vals = self.traffic.whatif(i)
                        slots[c] = self._submit(now, agg, values=vals,
                                                index=i)
                        i += 1
                        busy = True
                if not busy:
                    break
                if time.perf_counter() - stop_at > ANSWER_WAIT_S:
                    break
                time.sleep(0.001)
        done = [r.completed for r in self.reads if r.ok]
        self.t_end = max(done) if done else time.perf_counter()
        self.stats1 = self.service.stats
        self.window_end = self.t_end

    def _submit(self, due: float, agg: int, *, vertex=None, values=None,
                index: int = -1, in_window: bool = True) -> Read:
        import jax

        from repro.serve.window_service import LoadShedError

        now = time.perf_counter()
        r = Read(due, now, None, agg, vertex, index, in_window)
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                r.ticket = self.service.submit(agg, vertex=vertex,
                                               values=values)
        except LoadShedError as e:
            r.error = e
        self.reads.append(r)
        return r

    def _open_loop(self) -> None:
        due, kinds = self.traffic.open_schedule(self.seconds)
        read_kind = self.traffic.kinds.index("point_read")
        n_reads = int((kinds == read_kind).sum())
        aggs, verts = self.traffic.window_reads(n_reads)
        work: queue.Queue = queue.Queue()
        writer = threading.Thread(target=self._writer, args=(work,),
                                  name="bench-writer", daemon=True)
        writer.start()
        with self._counting_compiles():
            try:
                self._schedule(due, kinds, read_kind, aggs, verts, work)
                self._drain()
            finally:
                work.put(None)
                writer.join(timeout=ANSWER_WAIT_S)
            self._wait_answers()
        done = [r.completed for r in self.reads if r.in_window and r.ok]
        self.t_end = max([self.window_end] + done)

    def _schedule(self, due, kinds, read_kind, aggs, verts, work) -> None:
        """Issue the window's operations when they are due."""
        import jax

        k = 0
        self.t0 = time.perf_counter() + 0.05
        with jax.profiler.TraceAnnotation("bench.window"):
            for d, kind in zip(due, kinds):
                target = self.t0 + float(d)
                self._sleep_until(target)
                self.lateness.append(time.perf_counter() - target)
                if kind == read_kind:
                    self._submit(target, int(aggs[k]), vertex=int(verts[k]))
                    k += 1
                else:
                    work.put(self._next_write(target))
            self._sleep_until(self.t0 + self.seconds)
        self.window_end = self.cut = self.t0 + self.seconds
        self.stats1 = self.service.stats

    def _drain(self) -> None:
        """Reads go on at the cell's rate, outside the window, until a read
        has seen every write of the window."""
        gap = 1.0 / float(self.mix["rate_per_s"])
        t = self.window_end
        while time.perf_counter() < self.window_end + DRAIN_MAX_S:
            if len(self.writes) == self.n_writes and self._writes_seen():
                break
            t += gap
            self._sleep_until(t)
            agg, v = self.traffic.reads(1)
            self._submit(t, int(agg[0]), vertex=int(v[0]), in_window=False)

    def _writer(self, work: queue.Queue) -> None:
        for w in iter(work.get, None):
            self._apply(w)

    def _writes_seen(self) -> bool:
        last = max((w.version for w in self.writes if w.in_window),
                   default=-1)
        return last < 0 or any(r.ok and r.ticket.version >= last
                               for r in self.reads)

    def _wait_answers(self) -> None:
        deadline = time.perf_counter() + ANSWER_WAIT_S
        for r in self.reads:
            if r.ticket is not None:
                r.ticket._event.wait(max(deadline - time.perf_counter(), 0))

    @staticmethod
    def _sleep_until(target: float) -> None:
        import jax

        delay = target - time.perf_counter()
        if delay > 0:
            with jax.profiler.TraceAnnotation("bench.await"):
                time.sleep(delay)

    # ------------------------------------------------------------------ #
    #  End-to-end metrics
    # ------------------------------------------------------------------ #
    def end_to_end(self) -> dict:
        """Every end-to-end number the traffic gives; the cell reports those
        that ``BENCHMARK.json`` names, and the notes keep the others."""
        win = [r for r in self.reads if r.in_window]
        ok = [r for r in win if r.ok]
        # a read that failed or never came misses any limit: it counts as
        # late as the run's last wait for it
        lat = [(r.completed if r.ok else self.t_giveup) - r.due for r in win]
        out = {"setup_s": self.setup_s,
               "reads_per_s": len(ok) / (self.t_end - self.t0)}
        if self.mix["loop"] == "open":
            out["read_p50_ms"] = quantile(lat, 0.5) * 1e3
            out["read_p95_ms"] = quantile(lat, 0.95) * 1e3
            self.notes["lateness_ms"] = {
                "median": quantile(self.lateness, 0.5) * 1e3,
                "p95": quantile(self.lateness, 0.95) * 1e3,
                "max": max(self.lateness, default=0.0) * 1e3}
            fresh = self._freshness()
            if fresh:
                out["freshness_ms"] = float(np.mean(fresh)) * 1e3
        self.notes.update(reads=len(win), reads_ok=len(ok),
                          writes=sum(w.in_window for w in self.writes),
                          compiles_in_window=self.compiles)
        return out

    def _freshness(self) -> list:
        """Per window write: from when it was due to the first completed
        read served at its version or later (the end of the drain when no
        read saw it)."""
        done = [(r.ticket.version, r.completed) for r in self.reads if r.ok]
        end = max([c for _, c in done] + [time.perf_counter()])
        out = []
        for w in self.writes:
            if not w.in_window:
                continue
            seen = [c for v, c in done if v >= w.version] \
                if w.error is None else []
            out.append((min(seen) if seen else end) - w.due)
        return out

    # ------------------------------------------------------------------ #
    #  Per-layer metrics
    # ------------------------------------------------------------------ #
    def per_layer(self) -> tuple:
        import jax

        summary = None
        if self.trace:
            summary = xtrace.reduce(xtrace.Trace.load(
                xtrace.find_xplane(str(self.trace_dir))))
        spans = []
        for e in self.tracer.events():
            if e.get("ph") != "X":
                continue
            start = self.tracer_epoch + e["ts"] / 1e6
            spans.append({"name": e["name"], "start": start,
                          "seconds": e["dur"] / 1e6, "args": e["args"]})
        ctx = {
            "config": self.config, "mix": self.mix,
            "window": (self.t0, self.window_end), "cut": self.cut,
            "spans": spans,
            "stats0": self.stats0, "stats1": self.stats1,
            "bucket": self.bucket, "counts": self.counts,
            "device_kind": jax.devices()[0].device_kind,
            "trace": summary,
        }
        out = {}
        for m in self.cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out, summary

    # ------------------------------------------------------------------ #
    #  The comparison that decides `correct`
    # ------------------------------------------------------------------ #
    def check(self) -> dict:
        """Each number compared, with its limit."""
        win = [r for r in self.reads if r.in_window]
        unanswered = sum(not r.ok for r in win)
        compared, mismatched = 0, 0
        points = [r for r in win if r.ok and r.vertex is not None]
        if points:
            c, m = self._check_points(points)
            compared += c
            mismatched += m
        whatifs = [r for r in win if r.ok and r.vertex is None]
        if whatifs:
            c, m = self._check_whatifs(whatifs)
            compared += c
            mismatched += m
        checks = {
            "mismatched": {"value": mismatched, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0},
        }
        if self.writes:
            checks["failed_writes"] = {
                "value": sum(w.error is not None for w in self.writes),
                "limit": 0}
            checks["stale_reads"] = {"value": self._stale_reads(),
                                     "limit": 0}
        self.notes["compared"] = compared
        return checks

    def _stale_reads(self) -> int:
        """Reads submitted after a write was acknowledged but served at an
        older version."""
        acks = sorted((w.acked, w.version) for w in self.writes
                      if w.error is None)
        times = np.array([a for a, _ in acks])
        vers = np.maximum.accumulate(np.array([v for _, v in acks]))
        stale = 0
        for r in self.reads:
            if not r.ok:
                continue
            k = int(np.searchsorted(times, r.submitted, side="right"))
            if k and r.ticket.version < vers[k - 1]:
                stale += 1
        return stale

    def _values_at(self, version: int) -> np.ndarray:
        """The attribute after the first ``version`` writes."""
        vals = self.values0.copy()
        for _, vertex, value in (w.op for w in self.writes[:version]):
            vals[vertex] = value
        return vals

    def _references(self, values, indptr, members, aggs) -> tuple:
        """Per aggregate index: the reference answers over the windows, and
        the control's (None without a control)."""
        names = self.config["aggregates"]
        ref = {a: oracle.reduce(values, indptr, members, names[a],
                                oracle.DTYPES[self.config["precision"]])
               for a in aggs}
        ctl = None if self.control is None else {
            a: oracle.reduce(values, indptr, members, names[a],
                             oracle.DTYPES[self.control]) for a in aggs}
        return ref, ctl

    def _check_points(self, points) -> tuple:
        by_version = defaultdict(list)
        for r in points:
            by_version[r.ticket.version].append(r)
        # writes set values only: every version has the same windows
        verts = np.unique([r.vertex for r in points])
        pos = {int(v): i for i, v in enumerate(verts)}
        indptr, members = oracle.windows(self.ref_graph,
                                         self.config["window"], verts)
        mismatched = 0
        for version, rs in sorted(by_version.items()):
            ref, ctl = self._references(self._values_at(version), indptr,
                                        members, {r.agg for r in rs})
            for r in rs:
                i = pos[r.vertex]
                got = (np.float32(r.ticket.result) if ctl is None
                       else ctl[r.agg][i])
                mismatched += int(got != ref[r.agg][i])
        return len(points), mismatched

    def _check_whatifs(self, whatifs) -> tuple:
        """Every explicit-values answer (or a seeded sample of
        ``check.answers`` of them) at every vertex (or a seeded sample of
        ``check.vertices``)."""
        chk = self.config["check"]
        rng = np.random.default_rng([self.seed, 9])
        n = self.config["graph"]["n"]
        if len(whatifs) > chk["answers"]:
            pick = rng.choice(len(whatifs), chk["answers"], replace=False)
            whatifs = [whatifs[i] for i in sorted(pick)]
        verts = (np.arange(n) if chk["vertices"] is None
                 or chk["vertices"] >= n
                 else np.sort(rng.choice(n, chk["vertices"], replace=False)))
        indptr, members = oracle.windows(self.ref_graph,
                                         self.config["window"], verts)
        compared = mismatched = 0
        for r in whatifs:
            _, vals = self.traffic.whatif(r.index)
            ref, ctl = self._references(vals, indptr, members, {r.agg})
            got = (np.asarray(r.ticket.result, np.float32)[verts]
                   if ctl is None else ctl[r.agg])
            compared += ref[r.agg].size
            mismatched += int((got != ref[r.agg]).sum())
        return compared, mismatched


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_accelerator(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoAccelerator(f"{len(devs)} devices, the cell asks for {chips}")


def execute(cell: Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, accelerator: bool = True, **kw) -> dict:
    """One whole run; returns the result line's object and prints the
    earlier lines (notes, then each number compared and its limit) to
    standard error."""
    if accelerator:
        require_accelerator(cell.workload["chips"])
    run = Run(cell, seed, seconds, trace, t_start=t_start, **kw)
    run.setup()
    run.run()
    e2e = run.end_to_end()
    if trace:
        metrics, summary = run.per_layer()
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
        summary = None
    run.notes.update({k: v for k, v in e2e.items()
                      if k not in metrics and k != "setup_s"})
    checks = run.check()
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    win = [r for r in run.reads if r.in_window]
    device = device_info()
    device["memory_peak_bytes"] = run.memory_peak_bytes
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    print("notes: " + json.dumps(run.notes, default=float), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    result = {
        "correct": bool(correct),
        "attempted": len(win) + sum(w.in_window for w in run.writes),
        "failed": sum(not r.ok for r in win)
        + sum(w.in_window and w.error is not None for w in run.writes),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = summary["breakdown"]
    result["checks"] = checks
    return result
