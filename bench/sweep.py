#!/usr/bin/env python3
"""Find a cell's knee: run it at several offered loads in one process.

    python3 bench/sweep.py --workload qwen2.5-3b.code \\
        --key rate --values 1.5,2,3 --seconds 20

``--key`` names a key of the traffic file's ``arrivals`` (``rate`` for
Poisson requests).  Each value is a whole run of the cell with that key
changed and a small checked sample; its ``load:`` line on standard error
says whether the backlog grew over the window.  The rates the cells run at were chosen from such a
sweep (PERF.md).
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
    ap.add_argument("--key", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=2147483647)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from bench.harness import common

    manifest, cell, config, traffic = common.find_cell(args.workload)
    from bench.harness import llm as face

    sample = {"requests": 1, "rows": 1}
    for v in [float(x) for x in args.values.split(",")]:
        arr = dict(traffic["arrivals"])
        arr[args.key] = v
        tf = dict(traffic, arrivals=arr, sample=sample)
        run = argparse.Namespace(workload=args.workload, seed=args.seed,
                                 seconds=args.seconds, trace=0,
                                 rehearse=args.rehearse, control=False)
        common.log(f"sweep {args.key}={v}")
        face.run(run, manifest, cell, config, tf,
                 common.Clock(time.perf_counter()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
