"""Every cell of BENCHMARK.json, end to end at a tiny size on the CPU
(Pallas in interpret mode), with the harness's look for a chip skipped."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
N = 256  # vertices of the tiny graphs (a power of two, as R-MAT needs)
KEYS = ("correct", "attempted", "failed", "metrics", "device")


def run_cell(name, seconds=1.5, trace=False, tmp_path=None, **kw):
    from bench.cell import execute

    from bench.cell import Cell

    return execute(Cell.find(name, ROOT), 4_000_000_017, seconds, trace,
                   t_start=time.perf_counter(), accelerator=False, n=N,
                   compile_cache=False,
                   trace_dir=None if tmp_path is None else tmp_path / "trace",
                   **kw)


def test_every_name_is_found_in_its_file():
    from bench.cell import Cell, load_reader

    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["graph"]) | set(cfg)
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        cell = Cell.find(w["name"], ROOT)
        assert cell.mix["ops"], w["traffic"]
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for m in BENCH["per_layer"]:
        assert callable(load_reader(m["name"]))
        assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
    # every reader in bench/metrics loads
    for path in (ROOT / "bench" / "metrics").glob("[!_]*.py"):
        assert callable(load_reader(path.stem))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(cell, trace, tmp_path):
    from bench.cell import Cell

    res = run_cell(cell, trace=bool(trace), tmp_path=tmp_path)
    assert set(KEYS) <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    json.dumps(res)  # the result line is plain JSON
    names = set(res["metrics"])
    c = Cell.find(cell, ROOT)
    if trace:
        assert {"window_s", "busy_s"} <= set(res["device"])
        expected = {m["name"] for m in c.per_layer
                    if m["source"] != "device_trace"}
        # the CPU has no device plane: device-trace metrics stay silent
        assert names == expected
    else:
        assert names == {m["name"] for m in c.end_to_end}
        assert all(v["value"] > 0 for v in res["metrics"].values())


def _main(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_refuses_without_an_accelerator():
    out = _main(ROOT)
    assert out.returncode == 3 and out.stdout == "", out.stderr[-2000:]


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    out = _main(tmp_path)
    assert out.returncode != 0 and out.stdout == ""
