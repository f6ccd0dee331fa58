#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python3 chipbench/calibrate.py --workload <name> --rounds <n> \
        --seeds <s1> <s2> ... [--control 3] [--faults 3] [--fleet] \
        [--out <file>]

For each seed the cell's window call runs with ``n_rounds=<n>`` and is
compared with the plain reference (the lower reading).  On the first
``--control`` seeds the reference computed at the precision below the
configuration's stands in the program's place (the control, which has to
fail), and on the first ``--faults`` seeds each fault of ``faults.py`` is
planted under the window.  ``--fleet`` also reads the numbers of the
cell's check that its traffic file does not switch on yet (the LM's
fleet).  One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as RUN  # noqa: E402
import faults as FA  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--fleet", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    device = RUN.check_device(1)
    RUN.enable_cache()
    spec = RUN.load_cell(args.workload)
    if args.fleet:
        spec["traffic"]["check"]["fleet"] = True
    RUN.apply_precision(spec["config"])
    faults = FA.FAULTS[spec["config"]["plane"]]
    plane = RUN.load_module(spec["plane"], "plane")
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec.update(workload=args.workload, device=device["kind"])
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i, seed in enumerate(args.seeds):
        sess = plane.Session(spec["config"], spec["traffic"], seed)
        sess.n_rounds = args.rounds
        t0 = time.perf_counter()
        sess.window()
        wall = time.perf_counter() - t0
        readings = sess.compare()
        emit({"seed": seed, "what": "program", "wall_s": wall,
              "compare_s": time.perf_counter() - t0 - wall,
              "readings": readings})
        if i < args.control:
            ctl = sess.control()
            emit({"seed": seed, "what": "control",
                  "readings": sess.compare(ctl)})
        if i < args.faults:
            for name, fault in faults.items():
                with fault():
                    sess.window()
                emit({"seed": seed, "what": f"fault:{name}",
                      "readings": sess.compare()})
        sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
