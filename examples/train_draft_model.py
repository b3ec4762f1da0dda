"""Train a ~1M-param draft model for a few hundred steps on the synthetic
task mixture, checkpoint it, and measure how its acceptance rate against
the cached target improves with training — the full training substrate
(data pipeline, AdamW, checkpointing) end to end.

Run:  PYTHONPATH=src python examples/train_draft_model.py
"""
import dataclasses

import numpy as np

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common
from repro.core.config import OptimizerConfig, TrainConfig
from repro.training.checkpoint import save_checkpoint
from repro.training.data import lm_batches
from repro.training.train import train_loop


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    cfg_t, _, pt, _, _ = common.build_pair("llama")   # cached target
    cfg_d = common.draft_config()
    stream = common.mixed_stream()
    prompts = common.dataset("code").prompts(6, 12, seed=1)

    pd = None
    for steps in (40, 120, 250):
        tc = TrainConfig(global_batch_size=16, seq_len=64,
                         optimizer=OptimizerConfig(learning_rate=3e-3,
                                                   warmup_steps=20,
                                                   total_steps=steps,
                                                   grad_clip=5.0))
        pd, m = train_loop(cfg_d, tc, lm_batches(stream, 16, 64, seed=11),
                           num_steps=steps, verbose=False, seed=11)
        res, _, _ = common.serve(cfg_t, cfg_d, pt, pd, prompts,
                                 policy="static", static_sl=4)
        print(f"draft @ {steps:3d} steps: loss={m['loss']:.3f}  "
              f"acceptance={res['mean_acceptance']:.2f}  "
              f"BE={res['block_efficiency']:.2f}")

    path = save_checkpoint("/tmp/repro_example_draft", 250, pd)
    print(f"checkpointed trained draft to {path}")


if __name__ == "__main__":
    main()
