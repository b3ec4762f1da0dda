#!/usr/bin/env python3
"""Greedy-stream exactness over several prompt seeds and matmul precisions.

    python tools/exactness_probe.py --seeds 0 1 2 --precision default highest
    python tools/exactness_probe.py --reduced --requests 3 --max-new 8

Runs ``chip_smoke.py``'s serving runs (smollm-135m at published widths,
or its ``.reduced()`` miniature) for each engine matmul precision
(``ServingConfig.matmul_precision``) and prompt seed, and
prints one JSON line for every place a ``dsde`` stream leaves the
``autoregressive`` stream of its pool, with how far the two tokens sit
below the top logit (``below_std``: in logit standard deviations), then
one summary line per (precision, seed).  It asserts nothing: it measures
what ``chip_smoke.py`` checks, past the first failure.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[0])
    ap.add_argument("--precision", nargs="+", default=["default"],
                    choices=["default", "high", "highest"])
    ap.add_argument("--reduced", action="store_true",
                    help="the .reduced() miniature at the demo's sizes")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    smoke = _chip_smoke()
    cfg = get_config("smollm-135m")
    if args.reduced:
        cfg = cfg.reduced()
    for precision in args.precision:
        for seed in args.seeds:
            pt, prompts, streams = smoke.serving_streams(
                cfg, full=not args.reduced, requests=args.requests,
                max_new=args.max_new, seed=seed, precision=precision)
            counts = {}
            for (pool, policy, drafter), got in streams.items():
                if policy == "autoregressive":
                    continue
                name = f"{pool}/{policy}/{drafter}"
                found = smoke.divergences(
                    prompts, got, streams[pool, "autoregressive", "ngram"],
                    smoke._next_logits(cfg, pt, "int8" if pool == "int8"
                                       else "none", precision=precision))
                for d in found:
                    print(json.dumps({
                        "precision": precision, "seed": seed, "run": name,
                        **d, "below_std": d["below_top_logit"]
                        / d["logit_std"]}), flush=True)
                counts[name] = len(found)
            print(json.dumps({"precision": precision, "seed": seed,
                              "requests": args.requests,
                              "tokens": args.max_new,
                              "divergences": counts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
