"""What every cell shares: finding its files by name, the seed's key, the
compile counter, the measured window and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration is the file that the ``configs`` entry names, its traffic mix
is ``bench/mixes/<traffic>.json``, the mix's ``kind`` names the driver
``bench/drivers/<kind>.py``, the configuration's generator is
``bench/generators/<kind>.py``, and a per-layer metric ``<stem>.<kind>`` is
read by ``bench/metrics/<stem>.py``, one reader for each quantity whatever
kind of cell reports it. Adding a cell adds files and entries; it edits none
of these.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# one lowering of a jaxpr to a program, then a compile or a cache read
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
WINDOW_SPAN = "bench.window"


def load_module(path: Path):
    """Import a Python file by path (file names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration, mix and
    metric entries, all found by name."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = root
        self.spec = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        self.name = name
        self.workload = cells[name]
        configs = {c["name"]: c for c in self.spec["configs"]}
        self.config = json.loads(
            (root / configs[self.workload["config"]]["file"]).read_text())
        self.mix = json.loads(
            (root / "bench" / "mixes" / f"{self.workload['traffic']}.json")
            .read_text())
        self.chips = int(self.workload["chips"])

    def _mine(self, metrics):
        return [m for m in metrics
                if self.name in m.get("workloads", [self.name])]

    @property
    def end_to_end(self) -> list:
        return self._mine(self.spec["end_to_end"])

    @property
    def per_layer(self) -> list:
        return self._mine(self.spec["per_layer"])

    def driver(self):
        return load_module(self.root / "bench" / "drivers"
                           / f"{self.mix['kind']}.py")

    def reader(self, metric: str):
        stem = metric.split(".")[0]
        return load_module(self.root / "bench" / "metrics" / f"{stem}.py")


def seed_key(seed: int):
    """A JAX key from a seed of any size, through two 32-bit words."""
    import jax
    import numpy as np

    w0, w1 = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(w0)), int(w1))


class Compiles:
    """Counts programs lowered (each then compiled or read from the
    persistent cache) from the moment it is made."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == LOWERING_EVENT:
            self.count += 1


class Window:
    """The measured window: host clock, compile count and, with
    ``trace``, a profiler trace reduced to busy time and the breakdown."""

    def __init__(self, trace: bool, platform: str, compiles: Compiles):
        self.trace = trace
        self.platform = platform
        self.compiles = compiles
        self.t0 = self.t1 = None
        self.compiled = 0
        self.reduced = None
        self._dir = None
        self._ann = None

    def __enter__(self):
        import jax

        if self.trace:
            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self._c0 = self.compiles.count
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self.t1 = time.perf_counter()
        self.compiled = self.compiles.count - self._c0
        self._ann.__exit__(*exc)
        if self.trace:
            from bench import trace as tr

            jax.profiler.stop_trace()
            try:
                if exc[0] is None:
                    path = next(Path(self._dir).rglob("*.xplane.pb"))
                    self.reduced = tr.reduce_file(str(path), self.platform,
                                                  WINDOW_SPAN)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def peak_bytes(devices) -> int:
    """Peak device memory on the fullest of ``devices`` (0 where the
    backend keeps no count, as the CPU)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def emit(line: dict, checks: list) -> None:
    """Print the checks, each beside its limit, as the last lines of
    standard error, and the result as the last line of standard output."""
    line = dict(line)
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in checks}
    for name, value, limit in checks:
        print(f"check {name} = {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


@contextlib.contextmanager
def noted(label: str, out: dict):
    """Record the host seconds of a block under ``out[label]``."""
    t0 = time.perf_counter()
    yield
    out[label] = time.perf_counter() - t0
