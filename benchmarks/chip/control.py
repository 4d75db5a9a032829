"""Readings that set a cell's correctness limit (not run by the benchmark).

    python3 benchmarks/chip/control.py --workload qwen25_3b.decode_long \\
        --seed 101 --seconds 10 [--control w8a8] [--fault NAME]

A run of the cell at its own sizes and load (as ``run_cell.py`` makes it),
then, on the same sample of served requests, the checks of the served
tokens (the program's reading) and of the tokens that the control puts
first (``reference.CONTROLS``: ``w8a8`` is the control, the reference
computed in fp8).  With ``--fault`` the program runs
with that fault of ``faults.py`` planted.  One JSON line last.  The limit
lies between the program's largest reading and the control's smallest, each
over several seeds, one process each.
"""

from __future__ import annotations

import argparse
import json
import sys

import run_cell
from run_cell import manifest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="w8a8")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    bench = manifest.benchmark()
    cell = manifest.workload(bench, args.workload)
    conf = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    limits = manifest.limits(cell["name"])
    metrics = manifest.metrics_for(bench, cell["name"], False)
    run_cell.configure_jax()
    devices, peaks = run_cell.check_device(int(cell["chips"]))
    import repro.core as core

    compiles = run_cell.CompileCounter()
    core.init(pools={"default": 2, "prefill": 2, "io": 1})
    try:
        out = run_cell.run(cell, conf, mix, args.seed, args.seconds, False,
                           peaks, devices, metrics, limits, compiles,
                           fault=args.fault, control=args.control)
        print(json.dumps({"seed": args.seed, "fault": args.fault,
                          "correct": out["correct"], "checks": out["checks"],
                          "control": out["control"],
                          "metrics": out["metrics"]}), flush=True)
    finally:
        core.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
