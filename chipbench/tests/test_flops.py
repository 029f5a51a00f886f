"""Operation counts against values worked out by hand, through the
architecture's counts (``model_type`` ``qwen2``)."""
import pytest

import flops

# d 8, 2 heads of 4, 1 kv head, d_ff 16, 2 layers, vocab 10
C = {"model_type": "qwen2", "hidden_size": 8, "num_attention_heads": 2,
     "num_key_value_heads": 1, "intermediate_size": 16, "num_hidden_layers": 2,
     "vocab_size": 10}


def test_matmul_params():
    # per layer: wq 8*8 + wk, wv 2*8*4 + wo 8*8 + mlp 3*8*16 = 64+64+64+384
    assert flops.matmul_params(C) == 2 * 576 + 10 * 8


def test_causal_pairs():
    assert flops.causal_pairs(3) == 1 + 2 + 3
    assert flops.causal_pairs(5, start=3) == 4 + 5


def test_train_flops():
    # 6 N per token for 3 tokens, attention 3 passes of 4*H*hd*pairs*layers
    n = 2 * 576 + 80
    assert flops.train_flops(C, [3]) == 6 * n * 3 + 3 * (4 * 2 * 4 * 6 * 2)


def test_decode_flops():
    # 2 N per token for 2 tokens, attention over 9 cached keys in all
    n = 2 * 576 + 80
    assert flops.decode_flops(C, 2, 9) == 2 * n * 2 + 4 * 2 * 4 * 9 * 2


def test_unknown_device_kind_is_an_error():
    from peaks import peaks
    assert peaks("TPU v5 lite")["bf16_flops"] == pytest.approx(197e12)
    with pytest.raises(KeyError):
        peaks("TPU v9000")
