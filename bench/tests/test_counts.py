"""Operation and byte counts against hand-worked numbers."""
import pytest

from bench import counts, peaks

Q06 = dict(layers=28, d_model=1024, n_heads=16, n_kv_heads=8, head_dim=128,
           d_ff=3072, vocab=151936, tied=True)
Q32 = dict(layers=16, d_model=5120, n_heads=64, n_kv_heads=8, head_dim=128,
           d_ff=25600, vocab=151936, tied=False)


def test_qwen3_32b_layer_params():
    # attention 5120*8192*2 + 5120*1024*2 = 94.4 M; MLP 3*5120*25600 = 393.2 M
    assert counts.layer_matmul_params(Q32) == 94_371_840 + 393_216_000


def test_qwen3_06b_kv_per_token():
    # 28 layers x (K, V) x 8 heads x 128 x 2 bytes = 112 KiB
    assert counts.kv_bytes_per_token(Q06) == 112 * 1024


def test_qwen3_06b_params():
    # published size: 0.6 B with the tied 155.6 M embedding counted once
    assert counts.weight_params(Q06) == 596_049_920


def test_token_and_span_flops_agree():
    # a span is the sum of its tokens; the head only on the last one
    span = counts.span_flops(Q06, 100, 164, head_last=True)
    toks = sum(counts.token_flops(Q06, p, head=(p == 163))
               for p in range(100, 164))
    assert span == pytest.approx(toks)
    assert counts.span_flops(Q06, 5, 5, True) == 0.0


def test_token_flops_by_hand():
    mm = 2 * 28 * (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
                   + 3 * 1024 * 3072)
    attn = 4 * 28 * 16 * 128 * 10
    head = 2 * 1024 * 151936
    assert counts.token_flops(Q06, 9, head=True) == mm + attn + head


def test_step_min_bytes():
    w = counts.weight_params(Q06) * 2
    kv = 112 * 1024
    assert counts.step_min_bytes(Q06, [100, 300], 2) == w + kv * 400 + kv * 2


def test_peaks_known_and_unknown_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
