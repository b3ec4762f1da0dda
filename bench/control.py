#!/usr/bin/env python3
"""Readings behind the limits of ``correct``: the program's and the
control's, on several seeds of one cell, in one process.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1 2 3

For each seed it makes a run as ``bench/run.py`` does (a short window at
the cell's own load is enough), then prints one JSON line with the
numbers the program's run gives and the numbers the control gives: the
reference computed one precision step below the configuration's
(``bf16x3``), put in the program's place over the same prompts and
tokens (``check.control_numbers``), and the numbers with every served
token altered (``check.token_fault_numbers``).  The benchmark's own runs
never run either.  Later seeds in the process find every program compiled.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    harness.setup_process()
    for seed in args.seeds:
        t = time.monotonic()
        result = harness.run_cell(args.workload, seed, args.seconds, False,
                                  t, control=True)
        info = result.pop("_info")
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "program": {k: v["value"] for k, v in result["check"].items()},
            "control": info.pop("control"),
            "token_fault": info.pop("token_fault"),
            "metrics": result["metrics"],
            "device": result["device"], "run": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
