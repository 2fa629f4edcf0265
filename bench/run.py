#!/usr/bin/env python3
"""ECORE's benchmark: one cell of BENCHMARK.json, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It puts the checkout's ``src`` on the
path itself.  A measuring run needs a TPU with the chips the cell asks for,
and exits with code 3 and no result otherwise.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
traced sub-window.  The last line of standard output is the result; the
numbers compared with the plain reference are the last lines of standard
error.

``--rehearse`` runs on the CPU at a tiny size (fewer, narrower layers and
shorter sequences); it is a rehearsal of the control flow and measures
nothing.  ``--control`` puts the lower-precision control in the program's
place for the comparison (a run whose ``correct`` must come out false).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; measures nothing")
    ap.add_argument("--control", action="store_true",
                    help="compare the lower-precision control in the "
                         "program's place")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not in this checkout "
              f"({ROOT / 'src' / 'repro'} is missing)", file=sys.stderr)
        return 3

    from bench.harness import common

    manifest, cell, config, traffic = common.find_cell(args.workload)
    clock = common.Clock(T0)
    if traffic["face"] != "llm":
        raise SystemExit(f"bench: unknown face {traffic['face']!r}")
    from bench.harness import llm as face
    try:
        return face.run(args, manifest, cell, config, traffic, clock)
    except common.NoDevice as e:
        return int(e.code)


if __name__ == "__main__":
    sys.exit(main())
