"""Operations and bytes from shapes, against numbers worked by hand."""

from bench import flops

# internlm2-1.8b widths, one layer
C = {"num_layers": 1, "d_model": 2048, "num_heads": 16, "num_kv_heads": 8,
     "head_dim": 128, "d_ff": 8192, "vocab_size": 92544}


def test_matmul_params_one_layer():
    # wq 2048*16*128 + wk, wv 2*2048*8*128 + wo 16*128*2048 + 3 * 2048*8192
    layer = 4194304 + 4194304 + 4194304 + 50331648
    head = 2048 * 92544
    assert flops.matmul_params(C) == layer + head == 252_444_672


def test_train_flops_one_layer():
    # 6 N T, plus causal attention: 2 matmuls * 2 flops * B * H * hd * S(S+1)/2,
    # three times over for forward and backward
    tokens = 4 * 1024
    attn = 2 * 2 * 4 * 16 * 128 * (1024 * 1025 // 2)
    assert flops.attention_flops_forward(C, 4, 1024) == attn == 17_196_646_400
    assert flops.train_flops_per_step(C, 4, 1024) == 6 * 252_444_672 * tokens + 3 * attn


def test_weight_bytes_at_rest():
    shapes = [(2048, 8192), (2048,), (8, 2048)]
    n = 2048 * 8192
    assert flops.weight_bytes_at_rest(shapes, "bf16") == 2 * n + 4 * 2048 + 2 * 16384
    assert flops.weight_bytes_at_rest(shapes, "q4") == (
        n // 2 + 4 * (n // 128) + 4 * 2048 + 16384 // 2 + 4 * 128
    )
    assert flops.kv_bytes_per_entry(dict(C, num_layers=8)) == 8 * 2 * 8 * 128 * 2
