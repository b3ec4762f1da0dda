"""Serving launcher: one random-initialised target/draft pair behind the
serving engine, at the ``.reduced()`` size (the default, quick on a CPU)
or at the architecture's published widths (``--full``, for a chip).

Modes (one is required):

* ``--demo``       — serve a batch of random prompts through
                     ``ServingEngine.run`` and print the metrics dict.
* ``--http``       — stand the OpenAI-compatible HTTP front door
                     (DESIGN.md §14) over the same engine: continuous-
                     batching front-end + ``/v1/completions`` with SSE
                     streaming, until interrupted.
* ``--http-smoke`` — run one streaming + one non-streaming completion
                     through a real socket, print the result JSON, exit.

The production-mesh dry run is a separate program:
``python -m repro.launch.dryrun``.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --demo
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --full --demo --paged --pipelined
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --http --paged --pipelined
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, List, Optional, Tuple

# Serving sizes (max_batch_size, max_seq_len): the reduced demo, and the
# published-width engine sized for one 16 GB chip.
REDUCED_SIZES = (4, 256)
FULL_SIZES = (16, 2048)
KV_BLOCK = 16


def init_pair(cfg, with_draft: bool, mesh=None) -> Tuple[Any, Any]:
    """Random target params (seed 1) and, for a model drafter, a
    correlated draft: target + 0.03 x noise (seed 7), so acceptance is
    non-trivial.  Under ``mesh`` every leaf is created already sharded by
    the serving rules: no device ever holds the whole model."""
    import jax
    import jax.numpy as jnp
    from repro.models.module import init_params
    from repro.models.transformer import model_specs

    specs = model_specs(cfg)
    if mesh is None:
        def init(key):
            return init_params(specs, key, jnp.float32)
    else:
        from repro.launch import sharding as shd
        rules = shd.serve_rules(mesh, mesh.devices.size)
        # speclint: disable=JX004 (one program per model build)
        init = jax.jit(
            lambda key: init_params(specs, key, jnp.float32),
            out_shardings=shd.param_shardings(specs, mesh, rules))
    pt = init(jax.random.PRNGKey(1))
    if not with_draft:
        return pt, None
    noise = init(jax.random.PRNGKey(7))
    return pt, jax.tree_util.tree_map(lambda a, b: a + 0.03 * b, pt, noise)


def serving_config(full: bool, *, paged: bool = False,
                   kv_quant: str = "none", prefix_caching: bool = False,
                   pipelined: bool = False):
    """Engine sizes for the reduced demo or for published widths.  The
    paged pool (implied by prefix caching and int8 storage) holds half
    the dense engine's KV bytes."""
    from repro.core.config import ServingConfig
    batch, seq = FULL_SIZES if full else REDUCED_SIZES
    if not (paged or prefix_caching or kv_quant != "none"):
        return ServingConfig(max_batch_size=batch, max_seq_len=seq,
                             pipelined=pipelined)
    return ServingConfig(
        max_batch_size=batch, max_seq_len=seq, paged_kv=True,
        kv_block_size=KV_BLOCK, pipelined=pipelined,
        prefix_caching=prefix_caching, kv_quant=kv_quant,
        num_kv_blocks=batch * (seq // KV_BLOCK) // 2)


def build_engine(cfg, params: Tuple[Any, Any], serving, *, policy: str,
                 drafter: str, mesh=None):
    """The serving engine over ``params = (target, draft or None)``."""
    from repro.core.config import SpecDecodeConfig
    from repro.serving.engine import ServingEngine
    pt, pd = params
    spec = SpecDecodeConfig(policy=policy, drafter=drafter)
    return ServingEngine(pt, cfg, pd, cfg if pd is not None else None,
                         spec, serving, mesh=mesh)


def demo_requests(cfg, n: int, max_new: int, rng, *,
                  prefix_share: float = 0.0,
                  deadline: Optional[float] = None) -> List[Any]:
    """``n`` random prompts of 6-19 tokens; with ``prefix_share`` > 0
    every prompt starts with one common head of whole KV blocks sized so
    head/(head+tail) ~= share."""
    from repro.serving.request import Request
    head: List[int] = []
    if prefix_share > 0:
        tail = 13                 # mean of the per-request draw below
        m = int(round(prefix_share / (1 - prefix_share) * tail))
        m = max(m // KV_BLOCK * KV_BLOCK, KV_BLOCK)
        head = rng.randint(0, cfg.vocab_size, size=m).tolist()
    return [Request(i, prompt=head + rng.randint(
        0, cfg.vocab_size, size=rng.randint(6, 20)).tolist(),
        max_new_tokens=max_new, slo_deadline_s=deadline)
        for i in range(n)]


def http_smoke(eng, cfg, model_name: str, rng, *, host: str = "127.0.0.1",
               port: int = 0, max_tokens: int = 8) -> dict:
    """One streaming and one non-streaming completion through a real
    socket against a front-end over ``eng``; returns the check's JSON."""
    from repro.serving.frontend import ServingFrontend
    from repro.serving.server import smoke_check, start_http_server_thread

    fe = ServingFrontend(eng).start()
    port, stop = start_http_server_thread(
        fe, host=host, port=port, model_name=model_name,
        default_max_tokens=max_tokens)
    try:
        prompt = rng.randint(0, cfg.vocab_size, size=8).tolist()
        out = smoke_check(host, port, prompt, max_tokens=max_tokens)
        out["port"] = port
        out["summary"] = {
            k: round(v, 4) if isinstance(v, float) else v
            for k, v in fe.summary().items()
            if k in ("requests_finished", "tokens_emitted", "rounds",
                     "ttft_mean_s", "queue_depth_peak")}
        return out
    finally:
        stop()
        fe.stop()


def _serve_forever(args, eng) -> None:
    from repro.serving.frontend import ServingFrontend
    from repro.serving.server import start_http_server_thread

    fe = ServingFrontend(eng).start()
    port, stop = start_http_server_thread(
        fe, host=args.host, port=args.port, model_name=args.arch,
        default_max_tokens=args.max_new)
    try:
        print(f"serving {args.arch} ({args.drafter} drafter, "
              f"{args.policy} policy) on "
              f"http://{args.host}:{port}/v1/completions", flush=True)
        while True:     # the HTTP server and engine loop run on daemons
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        stop()
        fe.stop()


def main() -> None:
    from repro.core.drafters import available_drafters
    from repro.core.policies import available_policies

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="serve the architecture at its published widths "
                         f"(batch {FULL_SIZES[0]}, max_seq_len "
                         f"{FULL_SIZES[1]}) instead of its .reduced() "
                         "miniature")
    ap.add_argument("--demo", action="store_true",
                    help="serve --requests random prompts and print the "
                         "run's metrics")
    ap.add_argument("--http", action="store_true",
                    help="serve the engine over the OpenAI-compatible "
                         "HTTP layer (/v1/completions, SSE streaming; "
                         "DESIGN.md §14) until interrupted")
    ap.add_argument("--http-smoke", action="store_true",
                    help="start the HTTP server on an ephemeral port, "
                         "run one streaming + one non-streaming "
                         "completion, print the result JSON, exit")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks an ephemeral port")
    ap.add_argument("--policy", default="dsde",
                    choices=list(available_policies()))
    ap.add_argument("--drafter", default="model",
                    choices=list(available_drafters()),
                    help="proposer for the speculation rounds (DESIGN.md "
                         "§9): 'model' runs a second draft model; "
                         "'ngram'/'self' serve with zero draft params")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--paged", action="store_true",
                    help="serve from the block-paged KV pool at half the "
                         "dense engine's KV bytes (DESIGN.md §4)")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    metavar="S",
                    help="demo only: fraction in [0,1) of every prompt "
                         "that is a common head; >0 implies --paged and "
                         "turns on refcounted prefix caching "
                         "(DESIGN.md §12)")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="paged-pool storage mode (DESIGN.md §13); int8 "
                         "stores K/V as per-block-scaled int8 and fuses "
                         "the dequant into the verify kv-sweep — implies "
                         "--paged")
    ap.add_argument("--slo-deadline", default=None, metavar="BASE,PER_TOK",
                    help="demo only: stamp every request with a "
                         "completion deadline of BASE + PER_TOK * "
                         "max_new_tokens seconds (DESIGN.md §15).  Pair "
                         "with --policy slo for deadline-aware "
                         "speculation; the run summary reports "
                         "slo_attained_frac / slo_goodput_tok_s and the "
                         "fitted latency-model coefficients either way")
    ap.add_argument("--pipelined", action="store_true",
                    help="plan/dispatch/collect pipelined schedule: "
                         "reconcile the host one round behind the device "
                         "(DESIGN.md §7); byte-identical greedy streams")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve under a (data, model) mesh, e.g. 1x4 or "
                         "2x2 (DESIGN.md §5).  Needs DxM visible devices; "
                         "on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N before "
                         "running.  Greedy streams stay byte-identical to "
                         "the single-device engine.")
    args = ap.parse_args()
    if not (args.demo or args.http or args.http_smoke):
        ap.error("choose --demo, --http or --http-smoke (the dry run is "
                 "python -m repro.launch.dryrun)")
    if not 0.0 <= args.prefix_share < 1.0:
        ap.error("--prefix-share must be in [0, 1)")
    deadline = None
    if args.slo_deadline:
        try:
            base_s, per_tok_s = map(float, args.slo_deadline.split(","))
        except ValueError:
            ap.error("--slo-deadline expects BASE,PER_TOK floats")
        deadline = base_s + per_tok_s * args.max_new

    import numpy as np
    from repro.configs import get_config
    from repro.core.config import SpecDecodeConfig
    from repro.core.drafters import build_drafter
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    mesh = None
    if args.mesh:
        from repro.launch.mesh import serving_mesh
        mesh = serving_mesh(args.mesh)
    spec = SpecDecodeConfig(policy=args.policy, drafter=args.drafter)
    params = init_pair(cfg, build_drafter(spec, cfg, cfg).uses_draft_model(),
                       mesh)
    serving = serving_config(args.full, paged=args.paged,
                             kv_quant=args.kv_quant,
                             prefix_caching=args.prefix_share > 0,
                             pipelined=args.pipelined)
    eng = build_engine(cfg, params, serving, policy=args.policy,
                       drafter=args.drafter, mesh=mesh)
    rng = np.random.RandomState(0)
    if args.http_smoke:
        print(json.dumps(http_smoke(eng, cfg, args.arch, rng, host=args.host,
                                    port=args.port, max_tokens=8)))
        return
    if args.http:
        _serve_forever(args, eng)
        return
    reqs = demo_requests(cfg, args.requests, args.max_new, rng,
                         prefix_share=args.prefix_share, deadline=deadline)
    m = eng.run(reqs)
    print({k: round(v, 3) if isinstance(v, float) else v
           for k, v in m.items()})


if __name__ == "__main__":
    main()
