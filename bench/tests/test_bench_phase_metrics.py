"""The phase readers on synthetic facts: each reads its quantity, and
returns None where the program or the run left nothing to read."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import pytest  # noqa: E402

from bench.harness import load_module  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def reader(stem):
    return load_module(ROOT / "bench" / "metrics" / f"{stem}.py")


def calls(**fields):
    """Two calls' stats with the given per-call values."""
    return [SimpleNamespace(**{k: v[i] for k, v in fields.items()})
            for i in range(2)]


PHASES = ["sample_s", "compact_s", "finish_s"]


@pytest.mark.parametrize("stem", PHASES)
def test_phase_mean_over_calls(stem):
    facts = {"calls": calls(**{stem: [1.0, 3.0]})}
    assert reader(stem).read(facts) == pytest.approx(2.0)


@pytest.mark.parametrize("stem", PHASES)
def test_phase_none_without_the_field(stem):
    """A program whose ConnectivityStats has no such field."""
    facts = {"calls": calls(edges_finish=[1, 2], finish_rounds=[2, 2])}
    assert reader(stem).read(facts) is None


@pytest.mark.parametrize("stem", PHASES)
def test_phase_none_without_calls(stem):
    assert reader(stem).read({"calls": []}) is None


def trace(gaps, window_s=10.0):
    return {"busy_s": 5.0, "window_s": window_s,
            "breakdown": {"device_ops": [], "idle_gaps": gaps}}


def test_held_idle_share_sums_the_serve_spans():
    facts = {"trace": trace([["(no host span)", 4.0],
                             ["connectit.serve.commit", 0.3],
                             ["connectit.serve.answer", 0.15],
                             ["connectit.serve.coalesce", 0.05],
                             ["np.asarray(jax.Array)", 0.2]])}
    assert reader("held_idle_share").read(facts) == pytest.approx(5.0)


@pytest.mark.parametrize("red", [
    None,                                         # an untraced run
    trace([["(no host span)", 4.0]]),             # a program without spans
    trace([["connectit.serve.commit", 1.0]], 0.0),  # an empty window
])
def test_held_idle_share_none(red):
    assert reader("held_idle_share").read({"trace": red}) is None


@pytest.mark.parametrize("name", ["sample_s.static", "compact_s.static",
                                  "finish_s.static", "held_idle_share.serve"])
def test_entry_lists_the_cell_that_reports_it(name):
    (m,) = [m for m in SPEC["per_layer"] if m["name"] == name]
    assert m["workloads"] == (["g500-s24.serve"] if name.endswith(".serve")
                              else ["g500-s22.static"])
