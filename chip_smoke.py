#!/usr/bin/env python3
"""Chip smoke test: the serving path on a TPU at published widths.

    python chip_smoke.py               # one chip: smollm-135m
    python chip_smoke.py --four-chips  # four chips: granite-8b on a 1x4 mesh

One chip runs three phases in one process:

1. kernels — the compiled paged verify kernels (fp and int8 pools)
   against their ``kernels/ref.py`` oracles within ``ATTN_ATOL`` /
   ``ATTN_RTOL``, and the n-gram kernel bit for bit, at the model's head
   layout, block 16, T = K+1 for K in {1, 4, 10};
2. serving — ``launch/serve.py``'s engine at published widths, random
   weights, paged pool, pipelined: 8 greedy requests of 32 new tokens.
   ``dsde`` with the ``model`` drafter and with ``ngram`` must each emit
   the ``autoregressive`` stream of the fp pool token for token;
   ``dsde`` with ``model`` on the int8 pool must emit the int8
   ``autoregressive`` stream, leaving it only at a near-tie of the
   target's logits (``TIE_TOL``, DESIGN.md §13);
3. http — one streaming and one non-streaming completion through a real
   socket over the same full-width engine.

``--four-chips`` runs only granite-8b over a (data=1, model=4) mesh:
params and round state are created sharded, the paged kernel runs per
chip on its own KV heads, and ``dsde`` with ``ngram`` must emit the
``autoregressive`` stream of the same mesh token for token.

Informational JSON lines (compile and wall seconds, round and token
counters) come first; the last line of stdout is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase
fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Attention kernels (f32 dots at full precision) vs the f32 ("highest")
# oracle over unit-variance inputs: summation-order noise only, with
# headroom; measured errors are printed for every case.
ATTN_ATOL = 1e-4
ATTN_RTOL = 1e-4
# On the int8 pool a greedy speculative stream may leave the
# autoregressive one only where both tokens' logits lie within this many
# logit standard deviations of the top logit.  The two schedules compute
# a token's K/V in programs of different row counts, whose f32 results
# differ in the last ulps; int8 rounding can turn such an ulp into one
# quantization step of a stored element (DESIGN.md §13).  A wrong token
# or KV entry moves logits by a sizeable share of their spread.  The fp
# pool and the mesh are held to token-for-token equality.
TIE_TOL = 0.01
KS = (1, 4, 10)
BLOCK = 16


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileClock:
    """Seconds the XLA backend spent compiling (a persistent-cache hit
    skips the backend compile and adds nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration


# ---------------------------------------------------------------------------
# phases (also driven at .reduced() widths on the CPU by tests)
# ---------------------------------------------------------------------------


def _paged_inputs(rng, b, t, h, kv, d, cols, quant):
    """Ragged block tables into a scrambled pool of ``b * cols`` blocks,
    and the query/slot positions of the last ``t`` tokens of each row."""
    import jax.numpy as jnp
    import numpy as np
    n = b * cols
    table = np.full((b, cols), -1, np.int32)
    kvp = np.full((n, BLOCK), -1, np.int32)
    qpos = np.zeros((b, t), np.int32)
    perm = rng.permutation(n)
    for i in range(b):
        nb = rng.randint(1, cols + 1)
        table[i, :nb] = perm[i * cols:i * cols + nb]
        ntok = rng.randint(t, nb * BLOCK + 1)
        for p in range(ntok):
            kvp[table[i, p // BLOCK], p % BLOCK] = p
        qpos[i] = np.arange(ntok - t, ntok)
    q = rng.standard_normal((b, t, h, d)).astype(np.float32)
    if quant:
        pools = [rng.randint(-127, 128, (n, BLOCK, kv, d)).astype(np.int8)
                 for _ in range(2)]
        pools += [rng.uniform(1e-3, 2e-2, (n, BLOCK, kv)).astype(np.float32)
                  for _ in range(2)]
    else:
        pools = [rng.standard_normal((n, BLOCK, kv, d)).astype(np.float32)
                 for _ in range(2)]
    return ([jnp.asarray(a) for a in [q, *pools]],
            [jnp.asarray(a) for a in (table, qpos, kvp)])


def kernel_phase(cfg, *, batch: int, table_cols: int, seq_len: int,
                 ngram_n: int = 3, interpret: bool = False,
                 seed: int = 0) -> dict:
    """The serving path's kernels, called directly (no dispatcher, no
    fallback), against their oracles.  Raises on any mismatch."""
    import jax
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.ngram_match import ngram_suffix_propose
    from repro.kernels.ragged_attention import (
        paged_ragged_verify_attention, paged_ragged_verify_attention_quant)

    rng = np.random.RandomState(seed)
    h, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    err = {"fp": 0.0, "int8": 0.0}
    for k in KS:
        for pool, kern, oracle in (
                ("fp", paged_ragged_verify_attention,
                 ref.paged_ragged_verify_attention_ref),
                ("int8", paged_ragged_verify_attention_quant,
                 ref.paged_ragged_verify_attention_quant_ref)):
            arrays, ctl = _paged_inputs(rng, batch, k + 1, h, kv, d,
                                        table_cols, pool == "int8")
            got = np.asarray(kern(*arrays, *ctl, interpret=interpret))
            with jax.default_matmul_precision("highest"):
                want = np.asarray(oracle(*arrays, *ctl))
            np.testing.assert_allclose(
                got, want, atol=ATTN_ATOL, rtol=ATTN_RTOL,
                err_msg=f"paged verify {pool} K={k}")
            err[pool] = max(err[pool], float(np.abs(got - want).max()))
        toks = jax.numpy.asarray(rng.randint(0, 8, (batch, seq_len)),
                                 jax.numpy.int32)
        ctx = jax.numpy.asarray(rng.randint(0, seq_len + 1, (batch,)),
                                jax.numpy.int32)
        got = ngram_suffix_propose(toks, ctx, n=ngram_n, k=k,
                                   interpret=interpret)
        want = ref.ngram_propose_ref(toks, ctx, n=ngram_n, k=k)
        for g, w, name in zip(got, want, ("tokens", "count")):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w),
                err_msg=f"ngram propose {name} K={k}")
    return {"cases": 3 * len(KS), "max_abs_err_fp": err["fp"],
            "max_abs_err_int8": err["int8"]}


def _serve(cfg, params, serving, *, policy, drafter, requests, max_new,
           mesh=None, seed=0):
    """One engine run through ``launch/serve.py``'s builders; returns the
    prompts, the token streams and the run's counters."""
    import numpy as np
    from repro.launch import serve
    eng = serve.build_engine(cfg, params, serving, policy=policy,
                             drafter=drafter, mesh=mesh)
    reqs = serve.demo_requests(cfg, requests, max_new,
                               np.random.RandomState(seed))
    m = eng.run(reqs)
    del eng
    gc.collect()    # free this engine's pools before the next one is built
    streams = [list(r.output) for r in reqs]
    bad = [r.request_id for r in reqs if len(r.output) != max_new]
    if bad or m["requests_finished"] != requests:
        raise RuntimeError(f"{policy}/{drafter}: requests {bad} did not emit "
                           f"{max_new} tokens ({m['requests_finished']} of "
                           f"{requests} finished)")
    counters = {k: m[k] for k in ("rounds", "tokens_emitted",
                                  "mean_acceptance", "block_efficiency",
                                  "wall_time_s", "preemptions")}
    return [list(r.prompt) for r in reqs], streams, counters


def _next_logits(cfg, params, kv_quant: str, mesh=None, precision=None):
    """``tokens -> logits`` of the token after ``tokens``, from one plain
    prefill program over a fresh paged pool of the given storage mode
    (the serving plane's own K/V rounding, DESIGN.md §13), at the
    engine's matmul precision (``ServingConfig.matmul_precision`` unless
    ``precision`` is given)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import prefill
    from repro.core.config import ServingConfig
    from repro.models import cache as cache_lib
    precision = precision or ServingConfig.matmul_precision

    def next_logits(tokens):
        nb = -(-len(tokens) // BLOCK)
        c = cache_lib.paged_cache_struct(cfg, 1, nb * BLOCK, nb, BLOCK,
                                         dtype=jnp.float32,
                                         kv_quant=kv_quant)
        if mesh is not None:
            c = jax.device_put(c, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
        with jax.default_matmul_precision(precision):
            _, last = prefill.prefill_paged_rows(
                params, cfg, c["k"], c["v"], c["kv_pos"],
                jnp.arange(nb, dtype=jnp.int32)[None],
                jnp.asarray([tokens], jnp.int32),
                jnp.asarray([len(tokens)], jnp.int32),
                k_scale=c.get("k_scale"), v_scale=c.get("v_scale"))
        return np.asarray(last[0, :cfg.vocab_size])
    return next_logits


def divergences(prompts, got, want, next_logits) -> list:
    """Where each stream of ``got`` first leaves its ``want`` stream: the
    request, the token index, both tokens, and how far the lower of the
    two sits below the top logit recomputed over the shared prefix
    (with the logits' standard deviation)."""
    out = []
    for i, (p, g, w) in enumerate(zip(prompts, got, want)):
        if g == w:
            continue
        j = next(j for j, (a, b) in enumerate(zip(g, w)) if a != b)
        logits = next_logits(p + w[:j])
        top = float(logits.max())
        out.append({"request": i, "diverged_at": j, "tokens": [g[j], w[j]],
                    "below_top_logit": max(top - float(logits[g[j]]),
                                           top - float(logits[w[j]])),
                    "logit_std": float(logits.std())})
    return out


def check_streams(name: str, found: list, tie_tol: float = 0.0) -> int:
    """Log each divergence of ``found`` and raise unless every one is a
    near-tie: both tokens within ``tie_tol`` logit standard deviations of
    the top logit (``tie_tol=0``: token-for-token equality).  Returns the
    number of near-ties admitted."""
    for d in found:
        log(check=name, **d)
    bad = [d for d in found if tie_tol <= 0
           or not d["below_top_logit"] <= tie_tol * d["logit_std"]]
    if bad:
        d = bad[0]
        raise RuntimeError(
            f"{name}: request {d['request']} leaves the autoregressive "
            f"stream at token {d['diverged_at']} ({d['tokens']}), "
            f"{d['below_top_logit']:.3g} below the top logit "
            f"(limit {tie_tol} x logit std {d['logit_std']:.3g})")
    return len(found)


SERVING_RUNS = (("fp", "autoregressive", "ngram"), ("fp", "dsde", "model"),
                ("fp", "dsde", "ngram"), ("int8", "autoregressive", "ngram"),
                ("int8", "dsde", "model"))


def serving_streams(cfg, *, full: bool, requests: int, max_new: int,
                    seed: int = 0, precision=None):
    """Run :data:`SERVING_RUNS` (paged pool, pipelined; matmuls at
    ``precision`` if given) over ``requests`` prompts drawn from ``seed``;
    returns the target params, the prompts and, per run, its greedy token
    streams (all of one length: ``_serve`` checks it)."""
    import dataclasses
    from repro.launch import serve
    pt, pd = serve.init_pair(cfg, with_draft=True)
    streams = {}
    for pool, policy, drafter in SERVING_RUNS:
        serving = serve.serving_config(
            full, paged=True, kv_quant="int8" if pool == "int8" else "none",
            pipelined=True)
        if precision is not None:
            serving = dataclasses.replace(serving, matmul_precision=precision)
        params = (pt, pd if drafter == "model" else None)
        prompts, streams[pool, policy, drafter], counters = _serve(
            cfg, params, serving, policy=policy, drafter=drafter,
            requests=requests, max_new=max_new, seed=seed)
        log(phase="serving", run=f"{pool}/{policy}/{drafter}", seed=seed,
            **counters)
    return pt, prompts, streams


def serving_phase(cfg, *, full: bool, requests: int = 8,
                  max_new: int = 32, seed: int = 0) -> dict:
    """Greedy ``dsde`` streams (model / ngram drafter on the fp pool,
    model on the int8 pool) must equal the ``autoregressive`` stream of
    the same pool type: token for token on the fp pool, up to near-ties
    (``TIE_TOL``) on the int8 pool."""
    pt, prompts, streams = serving_streams(cfg, full=full, requests=requests,
                                           max_new=max_new, seed=seed)
    ties = 0
    for pool in ("fp", "int8"):
        quant = "int8" if pool == "int8" else "none"
        for (p, policy, drafter), got in streams.items():
            if p != pool or policy == "autoregressive":
                continue
            found = divergences(prompts, got,
                                streams[pool, "autoregressive", "ngram"],
                                _next_logits(cfg, pt, quant))
            ties += check_streams(f"{pool}/{policy}/{drafter}", found,
                                  TIE_TOL if pool == "int8" else 0.0)
    return {"runs": len(streams), "int8_near_ties": ties}


def http_phase(cfg, *, full: bool, max_tokens: int = 8) -> dict:
    """One streaming and one non-streaming completion over a real socket
    against a full-width ``dsde``/``model`` engine; both must carry the
    same ``max_tokens`` tokens."""
    import numpy as np
    from repro.launch import serve
    params = serve.init_pair(cfg, with_draft=True)
    serving = serve.serving_config(full, paged=True, pipelined=True)
    eng = serve.build_engine(cfg, params, serving, policy="dsde",
                             drafter="model")
    out = serve.http_smoke(eng, cfg, cfg.name, np.random.RandomState(0),
                           max_tokens=max_tokens)
    if (out["non_streaming_tokens"] != out["streamed_tokens"]
            or len(out["streamed_tokens"]) != max_tokens):
        raise RuntimeError(f"http: streamed and non-streamed completions "
                           f"differ: {out}")
    return {"tokens": len(out["streamed_tokens"]),
            "events": out["events"], "finish_reason": out["finish_reason"]}


def four_chip_phase(cfg, mesh, *, batch: int = 8, requests: int = 4,
                    max_new: int = 16) -> dict:
    """``cfg`` at published widths over ``mesh``: greedy ``dsde`` with the
    ``ngram`` drafter must emit the ``autoregressive`` stream."""
    import dataclasses
    import jax
    from repro.launch import serve
    params = serve.init_pair(cfg, with_draft=False, mesh=mesh)
    serving = serve.serving_config(True, paged=True, pipelined=True)
    serving = dataclasses.replace(
        serving, max_batch_size=batch,
        num_kv_blocks=batch * serving.blocks_per_seq() // 2)
    streams = {}
    for policy in ("autoregressive", "dsde"):
        prompts, streams[policy], counters = _serve(
            cfg, params, serving, policy=policy, drafter="ngram",
            requests=requests, max_new=max_new, mesh=mesh)
        log(phase="four_chips", run=f"{policy}/ngram", **counters)
    check_streams("dsde/ngram", divergences(
        prompts, streams["dsde"], streams["autoregressive"],
        _next_logits(cfg, params[0], "none", mesh)))
    memory = [{k: d.memory_stats().get(k) for k in
               ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
              for d in mesh.devices.flat
              if d.memory_stats() is not None]
    return {"device_memory": memory,
            "devices": [str(d) for d in jax.devices()]}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _phase(clock, name, fn, *args, **kwargs):
    t0, c0 = time.monotonic(), clock.seconds
    result = fn(*args, **kwargs)
    log(phase=name, ok=True, wall_s=time.monotonic() - t0,
        compile_s=clock.seconds - c0, **result)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve granite-8b over a 1x4 (data, model) mesh "
                         "of four chips, and nothing else")
    args = ap.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no libtpu logs in /tmp
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    log(compile_cache=use_compile_cache())
    clock = CompileClock()
    if args.four_chips:
        from repro.launch.mesh import serving_mesh
        _phase(clock, "four_chips", four_chip_phase,
               get_config("granite-8b"), serving_mesh("1x4"))
    else:
        from repro.launch.serve import FULL_SIZES
        cfg = get_config("smollm-135m")
        batch, seq = FULL_SIZES
        _phase(clock, "kernels", kernel_phase, cfg, batch=batch,
               table_cols=seq // BLOCK, seq_len=seq)
        _phase(clock, "serving", serving_phase, cfg, full=True)
        _phase(clock, "http", http_phase, cfg, full=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
