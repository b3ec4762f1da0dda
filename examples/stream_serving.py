"""Async serving front-end demo (DESIGN.md §14): token streaming,
open-loop trace replay, and the replay-at-zero exactness check.

Three acts over one continuous-batching front-end
(``repro.serving.frontend``) wrapping the pipelined engine:

1. **Streaming** — requests submitted from the caller's thread against
   the live driver thread; each consumer iterates its
   :class:`StreamHandle` and sees tokens the moment the host
   reconciles them (per-token callbacks out of collect()).
2. **Trace replay** — a seeded bursty trace (benchmarks/loadgen.py)
   replayed open-loop at its arrival offsets, reporting TTFT/TPOT
   percentiles, queue depth, and goodput.
3. **Exactness** — the same trace with every arrival at t=0 must
   produce byte-identical streams to a direct ``ServingEngine.run()``:
   ``pump()`` is run()'s loop body, so the front-end adds concurrency,
   never different tokens.

Run:  PYTHONPATH=src python examples/stream_serving.py
      (first run trains the pair, ~3 min on CPU; cached afterwards)

      PYTHONPATH=src python examples/stream_serving.py --smoke
      (CI lane: untrained pair, tiny trace, seconds not minutes)

For the HTTP layer over this same front-end, see
``python -m repro.launch.serve --http`` (OpenAI-compatible, SSE).
"""
import argparse
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common, loadgen


def _engine(cfg_t, cfg_d, pt, pd):
    from repro.core.config import ServingConfig, SpecDecodeConfig
    from repro.serving.engine import ServingEngine

    spec = SpecDecodeConfig(policy="dsde", sf_normalize=True)
    sv = ServingConfig(max_batch_size=4, max_seq_len=256, paged_kv=True,
                       kv_block_size=16, pipelined=True)
    return ServingEngine(pt, cfg_t, pd, cfg_d, spec, sv, seed=0)


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.serving.frontend import ServingFrontend

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="untrained pair + tiny trace (CI lane)")
    args = ap.parse_args()

    label = "untrained (smoke)" if args.smoke else "trained (cached)"
    print(f"== building target/draft pair: {label} ==")
    if args.smoke:
        cfg_t, cfg_d, pt, pd, _ = common.untrained_pair()
        n_req, max_new = 6, 8
    else:
        cfg_t, cfg_d, pt, pd, _ = common.build_pair("llama")
        n_req, max_new = 12, 24

    # -- act 1: live token streaming ------------------------------------
    print("\n== streaming: consumers see tokens as rounds reconcile ==")
    fe = ServingFrontend(_engine(cfg_t, cfg_d, pt, pd)).start()
    prompts = common.dataset("dialogue").prompts(3, 12, seed=4)
    handles = [fe.submit(p, max_new_tokens=max_new) for p in prompts]
    lines = {}

    def _consume(i, handle):
        got = []
        for tok in handle:              # blocks until each token lands
            got.append(tok)
        lines[i] = got

    threads = [threading.Thread(target=_consume, args=(i, h))
               for i, h in enumerate(handles)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, h in enumerate(handles):
        print(f"  req {i}: {len(lines[i])} tokens streamed, "
              f"finish={h.request.finish_reason()}  "
              f"ttft={h.request.ttft() * 1e3:.0f}ms")
        assert lines[i] == h.request.output
    fe.stop()

    # -- act 2: open-loop bursty trace replay ---------------------------
    print("\n== trace replay: bursty arrivals, open loop ==")
    trace = loadgen.make_trace(n_req, rate_rps=4.0, process="bursty",
                               seed=13, max_new_cap=max_new)
    fe = ServingFrontend(_engine(cfg_t, cfg_d, pt, pd)).start()
    try:
        point = loadgen.replay(fe, trace)
    finally:
        fe.stop()
    print(f"  finished {point['requests_finished']}/{point['requests']} "
          f"({point['tokens_emitted']} tokens) in {point['wall_s']:.2f}s")
    print(f"  ttft p50/p99 = {point['ttft_s_p50'] * 1e3:.0f}/"
          f"{point['ttft_s_p99'] * 1e3:.0f} ms   "
          f"tpot p50 = {point['tpot_s_p50'] * 1e3:.0f} ms")
    print(f"  queue depth peak = {point['queue_depth_peak']:.0f}   "
          f"goodput = {point['goodput_tok_s']:.1f} tok/s "
          f"(SLO-attained {point['slo_attained_frac']:.0%})")

    # -- act 3: replay-at-zero == run() ---------------------------------
    print("\n== exactness: replay at t=0 vs direct run() ==")
    ref = loadgen.trace_requests(trace)
    _engine(cfg_t, cfg_d, pt, pd).run(ref)
    fe = ServingFrontend(_engine(cfg_t, cfg_d, pt, pd))
    reqs = loadgen.trace_requests(trace)
    for r in reqs:
        fe.submit_request(r)
    fe.run_until_drained()
    assert [r.output for r in reqs] == [r.output for r in ref], \
        "front-end replay diverged from run()"
    print("  token streams byte-identical: OK")


if __name__ == "__main__":
    main()
