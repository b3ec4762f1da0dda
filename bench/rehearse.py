#!/usr/bin/env python3
"""Memory rehearsal without the chip: compile a configuration's largest
round program and its largest prefill program for a described TPU v5e
and print what the compiler says each needs.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config granite-8b-l9
    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config granite-8b-l9 \
        --set serving.pool_fraction=0.5
    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config smollm-135m \
        --prefill-rows 80 --prefill-bucket 512

Nothing is allocated: weights and the engine's round state are shapes
(``jax.eval_shape``).  One JSON line per program holds the bytes of its
arguments, outputs and temporaries (``compiled.memory_analysis()``); the
device also holds the outputs of a program that does not donate its
arguments, so a round needs about arguments + outputs + temporaries.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _abstract(fn):
    import jax
    return lambda *a, **k: jax.eval_shape(functools.partial(fn, *a, **k))


def _override(cfg, items):
    for item in items:
        path, value = item.split("=", 1)
        *parents, leaf = path.split(".")
        d = cfg
        for p in parents:
            d = d[p]
        d[leaf] = json.loads(value)
    return cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON", help="override a configuration key")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--prefill-rows", type=int, default=None,
                    help="rows of the prefill program (default warm_rows)")
    ap.add_argument("--prefill-bucket", type=int, default=None,
                    help="its prompt bucket (default max_seq_len)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness
    from repro.core import prefill as prefill_lib
    from repro.core import spec_decode as sd

    cfg = _override(harness.load_config(args.config), args.set)
    # shapes only: the engine's state and the weights are never allocated
    sd.init_round_state = _abstract(sd.init_round_state)
    real_ref = harness.reference_module

    def reference_module(c):
        mod = real_ref(c)
        mod.init_weights = _abstract(mod.init_weights)
        return mod
    harness.reference_module = reference_module
    system = harness.build(cfg, 0)
    eng = system.engine

    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=args.topology).devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))

    def report(program, compiled, **shape):
        m = compiled.memory_analysis()
        print(json.dumps({
            "program": program, **shape,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "code_bytes": m.generated_code_size_in_bytes}), flush=True)

    print(json.dumps({"config": cfg["name"], "target_weight_bytes":
                      nbytes(eng.pt), "draft_weight_bytes":
                      nbytes(eng.pd) if eng.pd is not None else 0,
                      "round_state_bytes": nbytes(eng.state)}), flush=True)
    b = system.serving.max_batch_size
    k = eng.policy.max_bucket()
    with jax.default_matmul_precision(system.serving.matmul_precision):
        report("round", sd.spec_decode_round.lower(
            on_chip(eng.pt), on_chip(eng.pd), eng.cfg_t, eng.drafter,
            eng.spec, k, on_chip(eng.state),
            jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one)).compile(),
            bucket=k, batch=b)
        rows = args.prefill_rows or int(cfg["serving"]["warm_rows"])
        bucket = args.prefill_bucket or system.serving.max_seq_len
        width = system.serving.blocks_per_seq()
        cache = eng.state.target_cache

        def i32(shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
        report("prefill", prefill_lib.prefill_paged_rows.lower(
            on_chip(eng.pt), eng.cfg_t, on_chip(cache["k"]),
            on_chip(cache["v"]), on_chip(cache["kv_pos"]), i32((rows, width)),
            i32((rows, bucket)), i32((rows,)), plan=None).compile(),
            rows=rows, bucket=bucket)
    return 0


if __name__ == "__main__":
    sys.exit(main())
