"""Operation and byte counts against hand counts at one shape."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, harness  # noqa: E402

SHAPE = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
         "vocab_size": 10}


def test_matmul_params_hand_count():
    # per layer: q 8*4*2=64, k 8*2*2=32, v 32, o 4*2*8=64, mlp 3*8*16=384
    per_layer = 64 + 32 + 32 + 64 + 384
    assert flops.matmul_params(SHAPE) == 2 * per_layer + 8 * 10


def test_forward_flops_hand_count():
    # 3 tokens attending 1 + 2 + 3 = 6 positions in all
    want = 2 * flops.matmul_params(SHAPE) * 3 + 4 * 2 * 4 * 2 * 6
    assert flops.forward_flops(SHAPE, 3, 6) == want


def test_paged_attention_call_hand_count():
    # 2 rows x 3 queries, 40 stored positions swept, f32
    w = flops.paged_attention_call(SHAPE, rows=2, t=3, context_tokens=40)
    assert w["flops"] == 4 * 4 * 2 * 3 * 40
    assert w["bytes"] == (2 * 2 * 2 * 40 + 2 * 2 * 3 * 4 * 2) * 4


def test_kv_bytes_per_token_of_the_configurations():
    g = harness.load_config("granite-8b-l9")
    s = harness.load_config("smollm-135m")
    assert flops.kv_bytes_per_token(g) == 2 * 9 * 8 * 128 * 4 == 73728
    assert flops.kv_bytes_per_token(s) == 2 * 30 * 3 * 64 * 4 == 46080


def test_granite_l9_flops_per_token():
    g = harness.load_config("granite-8b-l9")
    per_layer = 4096 * (32 + 16) * 128 + 32 * 128 * 4096 + 3 * 4096 * 14336
    assert flops.matmul_params(g) == 9 * per_layer + 4096 * 49152
    assert 4.2e9 < flops.forward_flops(g, 1, 0) < 4.4e9
