"""Paper §4.4 at CPU scale: the low-acceptance (Gemma-27B/2B-like) regime.

A weak, divergently-trained draft makes speculation barely worthwhile
(k_opt collapses toward 2).  The example shows what the paper shows:
entropy-driven adaptation (AdaEDL) degrades, while the post-hoc KLD/WVIR
signal keeps DSDE near the static optimum.

Run:  PYTHONPATH=src python examples/low_acceptance_regime.py
"""
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common
from benchmarks.table3_latency_speedup import static_opt


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    for regime in ("llama", "gemma"):
        print(f"== {regime} pair "
              f"({'strong draft' if regime == 'llama' else 'weak, divergent draft'}) ==")
        cfg_t, cfg_d, pt, pd, ratio = common.build_pair(regime)
        prompts = []
        for name in ("code", "news", "dialogue"):
            prompts += common.dataset(name).prompts(3, 16, seed=4)

        sl_opt, lu_opt, m_opt = static_opt(cfg_t, cfg_d, pt, pd, prompts,
                                           ratio, 0.0)
        print(f"  static-opt: k_opt={sl_opt} latency_units={lu_opt:.1f} "
              f"acceptance={m_opt['mean_acceptance']:.2f}")
        for policy in ("dsde", "adaedl"):
            m, _, _ = common.serve(cfg_t, cfg_d, pt, pd, prompts,
                                   policy=policy)
            lu = common.latency_units(m, ratio)
            print(f"  {policy:8s}: latency_units={lu:.1f} "
                  f"(+{(lu / lu_opt - 1) * 100:.0f}% vs static-opt) "
                  f"acceptance={m['mean_acceptance']:.2f} "
                  f"BE={m['block_efficiency']:.2f}")


if __name__ == "__main__":
    main()
