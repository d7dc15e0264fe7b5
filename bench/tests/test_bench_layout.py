"""The harness finds a cell's configuration, mix, driver and metrics by
name, and a new cell needs new files and entries only."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench.harness import Cell, load_module  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_resolves():
    for w in SPEC["workloads"]:
        cell = Cell(w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        assert callable(cell.driver().run)
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
        assert "setup_s" in [m["name"] for m in cell.end_to_end]


def test_every_metric_names_cells_that_exist():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m["workloads"]) <= cells
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        stem = m["name"].split(".")[0]
        assert (ROOT / "bench" / "metrics" / f"{stem}.py").is_file()


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """Copy the benchmark, add a generator, a configuration, a mix, a metric
    and a cell as new files and new entries, and the harness finds each."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads(json.dumps(SPEC))
    bench = tmp_path / "bench"
    static = next(w for w in spec["workloads"] if w["name"].endswith(".static"))
    entry = next(c for c in spec["configs"] if c["name"] == static["config"])
    cfg = json.loads((tmp_path / entry["file"]).read_text())
    (bench / "generators" / "ring.py").write_text(
        "def edges(gen, key):\n    return None, None, gen['n']\n")
    cfg.update(name="g500-s19", generator={"kind": "ring", "n": 19})
    (bench / "configs" / "g500-s19.json").write_text(json.dumps(cfg))
    (bench / "mixes" / "closed_loop_twice.json").write_text(json.dumps(
        {"kind": "static", "warmup_calls": 2, "checked_calls": 2}))
    (bench / "metrics" / "lmax_share.py").write_text(
        "def read(facts):\n    return 42.0\n")
    spec["configs"].append({"name": "g500-s19", "source": "x",
                            "file": "bench/configs/g500-s19.json",
                            "reduced": ["scale"], "why": "x"})
    spec["workloads"].append({"name": "g500-s19.static", "config": "g500-s19",
                              "traffic": "closed_loop_twice", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "lmax_share.static", "unit": "%",
                              "better": "higher", "source": "program_counter",
                              "layer": "core.driver", "moves": "solve_s",
                              "workloads": ["g500-s19.static"]})
    solve = next(m for m in spec["end_to_end"] if m["name"] == "solve_s")
    solve["workloads"].append("g500-s19.static")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = Cell("g500-s19.static", tmp_path)
    assert cell.config["generator"]["n"] == 19
    ring = load_module(bench / "graphs.py").generator("ring")
    assert ring.edges(cell.config["generator"], None)[2] == 19
    assert cell.mix["warmup_calls"] == 2
    assert cell.driver().__name__.endswith("static")
    names = [m["name"] for m in cell.per_layer]
    assert "lmax_share.static" in names
    assert cell.reader("lmax_share.static").read({}) == 42.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "solve_s"]


def test_without_the_program_it_prints_nothing(tmp_path):
    """Only BENCHMARK.json and bench/: non-zero exit, no result line."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
