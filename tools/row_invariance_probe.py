#!/usr/bin/env python3
"""Does a token's result depend on how many rows share its program?

    python tools/row_invariance_probe.py --precision default highest
    python tools/row_invariance_probe.py --reduced

For smollm-135m at published widths (or its ``.reduced()`` miniature),
random weights, on whatever backend JAX finds, and for each matmul
precision:

* ``dot``: ``x [16*T, d_in] @ W [d_in, d_out]`` for each of the model's
  weight shapes and T in (1, 2, 3, 5, 11); the first 16 rows against T = 1;
* ``decode``: 16 sequences prefilled with the same prompts into a paged
  pool (fp, then int8), then one decode-mode forward that appends T
  tokens, the shape of a verify with K = T - 1 drafts (on a TPU it
  runs the compiled paged kernel).  For the first appended token,
  against T = 1: the largest logit difference, and how many elements of
  its stored K/V (all layers, k and v) differ, with the largest
  difference (int8 steps on the int8 pool).

One JSON line per (probe, shape or pool, precision), lists over T.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

BATCH, PROMPT, TS, BLOCK = 16, 40, (1, 2, 3, 5, 11), 16


def dot_probe(cfg, seed: int = 0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    h, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    shapes = [(cfg.d_model, h * d), (cfg.d_model, kv * d),
              (h * d, cfg.d_model), (cfg.d_model, cfg.d_ff),
              (cfg.d_ff, cfg.d_model), (cfg.d_model, cfg.vocab_size)]
    rng = np.random.RandomState(seed)
    dot = jax.jit(jnp.dot)
    for din, dout in shapes:
        w = jnp.asarray(rng.standard_normal((din, dout)) / din ** 0.5,
                        jnp.float32)
        x = jnp.asarray(rng.standard_normal((BATCH * max(TS), din)),
                        jnp.float32)
        ref = np.asarray(dot(x[:BATCH], w))
        diffs = [np.asarray(dot(x[:BATCH * t], w))[:BATCH] - ref
                 for t in TS]
        yield {"probe": "dot", "shape": [din, dout],
               "max_abs_diff": [float(np.abs(e).max()) for e in diffs],
               "n_diff": [int((e != 0).sum()) for e in diffs]}


def decode_probe(cfg, params, kv_quant: str, seed: int = 0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import prefill
    from repro.models import cache as cache_lib
    from repro.models.transformer import forward

    rng = np.random.RandomState(seed)
    prompts = rng.randint(0, cfg.vocab_size, (BATCH, PROMPT))
    extra = rng.randint(0, cfg.vocab_size, (BATCH, max(TS)))
    per_seq = -(-(PROMPT + max(TS)) // BLOCK)
    c = cache_lib.paged_cache_struct(cfg, BATCH, per_seq * BLOCK,
                                     BATCH * per_seq, BLOCK,
                                     dtype=jnp.float32, kv_quant=kv_quant)
    table = np.arange(BATCH * per_seq, dtype=np.int32).reshape(BATCH, -1)
    view, _ = prefill.prefill_paged_rows(
        params, cfg, c["k"], c["v"], c["kv_pos"], jnp.asarray(table),
        jnp.asarray(prompts, jnp.int32),
        jnp.full((BATCH,), PROMPT, jnp.int32),
        k_scale=c.get("k_scale"), v_scale=c.get("v_scale"))
    step = jax.jit(lambda p, v, t: forward(p, cfg, t, cache=v,
                                           mode="decode")[:2])
    blocks, slot = table[:, PROMPT // BLOCK], PROMPT % BLOCK

    def first_token(t):
        logits, new = step(params, view, jnp.asarray(extra[:, :t], jnp.int32))
        kv = [np.asarray(new[n])[:, blocks, slot].astype(np.float32)
              for n in ("k", "v")]
        return np.asarray(logits[:, 0, :cfg.vocab_size]), np.stack(kv)

    ref_logits, ref_kv = first_token(1)
    out = {"logit_max_diff": [], "kv_n_diff": [], "kv_max_diff": []}
    for t in TS:
        logits, kv = first_token(t)
        out["logit_max_diff"].append(float(np.abs(logits - ref_logits).max()))
        out["kv_n_diff"].append(int((kv != ref_kv).sum()))
        out["kv_max_diff"].append(float(np.abs(kv - ref_kv).max()))
    return {"probe": "decode", "kv_quant": kv_quant,
            "kv_elements": int(ref_kv.size),
            "logit_std": float(ref_logits.std()), **out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--precision", nargs="+", default=["default"],
                    choices=["default", "high", "highest"])
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.module import init_params
    from repro.models.transformer import model_specs
    use_compile_cache()
    cfg = get_config("smollm-135m")
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(model_specs(cfg), jax.random.PRNGKey(1),
                         jnp.float32)
    for precision in args.precision:
        with (contextlib.nullcontext() if precision == "default"
              else jax.default_matmul_precision(precision)):
            rows = list(dot_probe(cfg))
            rows += [decode_probe(cfg, params, q) for q in ("none", "int8")]
        for row in rows:
            print(json.dumps({"precision": precision,
                              "backend": jax.default_backend(), **row}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
