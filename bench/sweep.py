#!/usr/bin/env python3
"""Find an open-loop cell's knee: run it at several offered rates, in one
process, and print one JSON line per rate.

    python3 bench/sweep.py --workload <name> --seconds <s> --seed <n> \
        --rates 1 2 3 4

Each line holds the end-to-end metrics at that rate and ``drain_s``, the
seconds the requests due in the window took to finish after it closed:
past the knee the backlog grows through the window and ``drain_s`` with
it.  The rate a cell offers is written into its traffic file as a
number; this tool is for finding it again, not for the benchmark's runs.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness
    harness.setup_process()
    for rate in args.rates:
        out = harness.run_cell(args.workload, args.seed, args.seconds, False,
                               time.monotonic(),
                               overrides={"mix": {"rate_rps": rate}})
        info = out.pop("_info")
        print(json.dumps({
            "rate_rps": rate, "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "drain_s": info["drain_s"], "requests": info["requests"],
            "unfinished": info["unfinished"],
            "window_compiles": info["window_compiles"],
            "check": out["check"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
