#!/usr/bin/env python3
"""Readings for a cell's limits: the program's compared numbers and the
lower-precision control's, on many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 --seconds 8

Each seed is a whole run of the cell (``bench/run.py`` with ``--control``)
at a short window; its ``readings:`` line on standard error gives the
program's number and the control's for the same served requests.  The
limits in the configuration files were set from these readings (PERF.md).
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from bench.harness import common

    manifest, cell, config, traffic = common.find_cell(args.workload)
    from bench.harness import llm as face
    for seed in [int(x) for x in args.seeds.split(",")]:
        run = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0,
                                 rehearse=args.rehearse, control=True)
        common.log(f"readings seed {seed}")
        face.run(run, manifest, cell, config, traffic,
                 common.Clock(time.perf_counter()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
