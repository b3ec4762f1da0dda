"""Operations and bytes, computed from a configuration's shapes.

The yardstick for every utilization the benchmark reports: a share of a
peak is the work these functions count, over the time the trace or the
clock measured.  Sizes are the configuration file's (``hidden_size``,
``num_hidden_layers``, ...).
"""
from __future__ import annotations

from typing import Dict

F32 = 4


def _d(cfg: Dict):
    return (int(cfg["hidden_size"]), int(cfg["num_hidden_layers"]),
            int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]), int(cfg["intermediate_size"]),
            int(cfg["vocab_size"]))


def matmul_params(cfg: Dict) -> int:
    """Weights a token multiplies through: the attention projections and
    the SwiGLU MLP of every layer, and the output head."""
    d, L, H, KV, hd, f, V = _d(cfg)
    per_layer = d * (H + 2 * KV) * hd + H * hd * d + 3 * d * f
    return L * per_layer + d * V


def forward_flops(cfg: Dict, tokens: int, context_sum: int) -> float:
    """FLOPs of a forward over ``tokens`` tokens whose attended context
    lengths add up to ``context_sum``: two per multiply-add of every
    weight, and four per (query head, head dim, attended position) for
    the scores and the weighted sum of values."""
    d, L, H, KV, hd, f, V = _d(cfg)
    return (2.0 * matmul_params(cfg) * tokens
            + 4.0 * L * H * hd * context_sum)


def kv_bytes_per_token(cfg: Dict, itemsize: int = F32) -> int:
    """Bytes of keys and values one token keeps, over all layers."""
    d, L, H, KV, hd, f, V = _d(cfg)
    return 2 * L * KV * hd * itemsize


def paged_attention_call(cfg: Dict, rows: int, t: int, context_tokens: int,
                         itemsize: int = F32) -> Dict[str, float]:
    """One layer's call of the paged verify-attention kernel: ``rows``
    sequences of ``t`` query positions each, attending ``context_tokens``
    stored positions in all (the allocated pool tiles the kernel sweeps,
    which it reads whole).  Operations: scores and weighted values, per
    query head.  Bytes: each swept key and value once, the queries read
    and the outputs written once."""
    d, L, H, KV, hd, f, V = _d(cfg)
    flops = 4.0 * H * hd * t * context_tokens
    bytes_ = (2.0 * KV * hd * context_tokens + 2.0 * rows * t * H * hd) \
        * itemsize
    return {"flops": flops, "bytes": bytes_}
