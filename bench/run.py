"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell (device-built graph, session, warm-up of every shape the
window uses), measures for ``--seconds``, checks what the window produced
against the plain reference, and prints one JSON line last on standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``
and ``checks``. Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.

``--rehearse`` runs the same path on the CPU at the tiny sizes in the
configuration's and the mix's ``rehearsal`` blocks; its line names the CPU.
``--control`` puts the control of ``PERF.md`` in the program's place, to
show that the comparison fails it; the benchmark's own runs never set it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class Run:
    """What a driver gets: the cell, the arguments and the devices."""

    def __init__(self, cell, args, devices, compiles):
        self.cell = cell
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.control = args.control
        self.devices = devices
        self.platform = devices[0].platform
        self.compiles = compiles
        self.t_start = T_START
        self.config = dict(cell.config)
        self.mix = dict(cell.mix)
        if self.rehearse:
            for block in (self.config, self.mix):
                for k, v in block.get("rehearsal", {}).items():
                    block[k] = ({**block[k], **v} if isinstance(v, dict)
                                else v)

    def note(self, **facts) -> None:
        """An earlier line of the output: facts that are not metrics."""
        print(json.dumps(facts), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not at {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import Cell, Compiles, emit

    cell = Cell(args.workload, ROOT)
    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if not args.rehearse:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
                  f"found {len(devices)} {devices[0].platform} device(s)",
                  file=sys.stderr)
            return 3
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    run = Run(cell, args, devices, Compiles())
    out = cell.driver().run(run)

    metrics = {}
    if run.trace:
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(out["facts"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out["metrics"][m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices[:cell.chips]),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": all(v <= lim for _, v, lim in out["checks"]),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device}
    if run.trace:
        red = out["facts"]["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = red["breakdown"]
    emit(line, out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
