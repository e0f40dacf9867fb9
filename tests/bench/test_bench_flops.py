"""Model FLOP counts of the configurations, against hand-worked numbers."""
import numpy as np
import pytest

from bench.spec import BENCH, load_json, load_module

NEMO = load_json(BENCH / "configs" / "mistral-nemo-12b-8L.json")
DENSE = load_module(BENCH / "reference" / "dense.py")

# mistral-nemo-12b, 8 layers: per layer q/o 5120x4096 each, k/v 5120x1024
# each, MLP 3 x 5120x14336; LoRA r=32 on wq (5120+4096) and wv (5120+1024);
# head 5120 x 131072
LAYER = 5120 * 4096 * 2 + 5120 * 1024 * 2 + 3 * 5120 * 14336
LORA = 32 * (5120 + 4096) + 32 * (5120 + 1024)
HEAD = 5120 * 131072


def test_layer_sizes():
    assert LAYER == 272_629_760 and LORA == 491_520
    assert DENSE._matmul_params(NEMO) == (LAYER, LORA, HEAD)


def test_decode_row():
    # one row decoding at position 1000: all matmuls once, attention over
    # 1001 keys (4 x 1001 x 32 heads x 128 per layer), the head once
    want = 2 * 8 * (LAYER + LORA) + 4 * 1001 * 4096 * 8 + 2 * HEAD
    assert want == 5_843_320_832
    got = DENSE.serve_step_flops(NEMO, np.array([1000, 0]), np.array([1, 0]))
    assert got == want


def test_prefill_chunk():
    # a 256-token chunk resumed at position 512: keys 513..768 per token
    keys = sum(range(513, 769))
    want = 2 * 8 * (LAYER + LORA) * 256 + 4 * keys * 4096 * 8 + 2 * HEAD
    got = DENSE.serve_step_flops(NEMO, np.array([512]), np.array([256]))
    assert got == pytest.approx(want, rel=1e-12)


def test_train_step():
    # 8 rows of 1024: forward + activation gradients 4 x weights a token,
    # LoRA 6 x its weights, causal attention 3 x 4 x keys x 4096 x 8 layers
    per_token = 4 * (8 * LAYER + HEAD) + 6 * 8 * LORA
    attn = 3 * 4 * 4096 * 8 * (1024 * 1025 // 2)
    want = 8 * (1024 * per_token + attn)
    assert want == 95_302_639_943_680
    assert DENSE.train_step_flops(NEMO, 8, 1024) == pytest.approx(want,
                                                                  rel=1e-12)
