"""Each cell rehearsed on the CPU at a tiny size: a well-formed last line,
``correct`` false under the control and under each fault planted in the
program's timed path, and no result without a TPU."""

import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from bench import run as bench_run  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def rehearse(capsys, cell, *extra, seconds=0.5, trace=0):
    seconds = 1.5 if cell.endswith(".serve") else seconds
    rc = bench_run.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", str(seconds), "--trace", str(trace),
                         "--rehearse", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def well_formed(line, cell, trace):
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    want = SPEC["per_layer"] if trace else SPEC["end_to_end"]
    want = {m["name"]: m["unit"] for m in want
            if cell in m.get("workloads", [cell])}
    if trace:
        assert line["device"]["window_s"] > 0 < line["device"]["busy_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert set(line["metrics"]) <= set(want)
    else:
        assert set(line["metrics"]) == set(want)
    for name, m in line["metrics"].items():
        assert m["unit"] == want[name]
        assert isinstance(m["value"], (int, float))
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_line(capsys, cell, trace):
    line = rehearse(capsys, cell, trace=trace)
    well_formed(line, cell, trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(capsys, cell):
    assert rehearse(capsys, cell, "--control")["correct"] is False


def _static_fault(kind):
    from repro.core import driver

    orig = driver.run_connectivity

    def broken(g, *a, **kw):
        if kind == "half":
            s, r = g.senders, g.receivers
            drop = ((jnp.minimum(s, r) * 7 + jnp.maximum(s, r)) % 2) == 1
            s, r = jnp.where(drop, g.n, s), jnp.where(drop, g.n, r)
            g = dataclasses.replace(g, senders=s, receivers=r, indices=r)
        labels, stats = orig(g, *a, **kw)
        if kind == "unchanged":
            labels = jnp.arange(g.n, dtype=labels.dtype)
        if kind == "altered":
            labels = labels.at[-1].add(1)
        return labels, stats

    return driver, "run_connectivity", broken


def _serve_fault(kind):
    import numpy as np

    from repro.serve.snapshot import SnapshotStore

    if kind == "altered":
        orig = SnapshotStore.query

        def query(self, qa, qb):
            ans, epoch = orig(self, qa, qb)
            return ans.at[0].set(~ans[0]), epoch

        return SnapshotStore, "query", query
    orig = SnapshotStore.begin_commit

    def begin_commit(self, u, v, *a):
        u, v = np.array(u, np.int32), np.array(v, np.int32)
        cut = 0 if kind == "unchanged" else u.shape[0] // 2
        u[cut:] = self.n
        v[cut:] = self.n
        return orig(self, u, v, *a)

    return SnapshotStore, "begin_commit", begin_commit


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_fault_in_the_timed_path_fails(capsys, monkeypatch, cell, kind):
    """A step that leaves its state unchanged, half of each batch left out,
    one answer altered where it is produced. (One chip: no exchange
    between chips to leave out.)"""
    fault = _serve_fault if cell.endswith(".serve") else _static_fault
    monkeypatch.setattr(*fault(kind))
    assert rehearse(capsys, cell)["correct"] is False


def test_without_a_tpu_no_result(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out.strip() == ""
