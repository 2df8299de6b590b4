"""The peak table and the operation and byte counts, against numbers
worked out by hand at small shapes."""
import pytest

from bench import flops, peaks


def test_peaks_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


DENSE = {"family": "dense", "d_model": 8, "num_layers": 2, "num_heads": 2,
         "num_kv_heads": 2, "head_dim": 4, "d_ff": 16, "act": "swiglu",
         "vocab_size": 30, "pad_vocab_multiple": 16}
SSM = {"family": "ssm", "d_model": 8, "num_layers": 2, "ssm_expand": 2,
       "ssm_head_dim": 4, "ssm_ngroups": 1, "ssm_state": 3, "ssm_conv": 4,
       "vocab_size": 30, "pad_vocab_multiple": 16}


def test_dense_counts():
    # per layer: q,k,v 8x(2+2+2)x4 = 192, o 8x8 = 64, MLP 3x8x16 = 384
    # -> 640; 2 layers 1280; head 8 x 32 (30 padded to 32) = 256
    assert flops.matmul_params(DENSE) == 1536
    # seq 3: mean keys 2; attention 4 x 2 heads x 4 x 2 = 64 per layer
    assert flops.train_flops_per_token(DENSE, 3) == 3 * (2 * 1536 + 2 * 64)
    # live 5, batch 2: weights 2 B x 1536; k and v 2 B x 2 layers x batch
    # 2 x 2 x (2 heads x 4) x 5 = 640; logits 2 B x 2 x 32 = 128
    assert flops.decode_bytes(DENSE, 2, 5) == 3072 + 640 + 128
    assert flops.decode_flops(DENSE, 2, 5) == 2 * (3072 + 2 * 4 * 2 * 4 * 5)


def test_ssm_counts():
    # d_in 16, heads 4; per layer in-proj 8 x (32 + 6 + 4) = 336, out 128
    # -> 464; 2 layers 928; tied head 8 x 32 = 256
    assert flops.matmul_params(SSM) == 1184
    # mixer: 4 x 4 heads x 4 x 3 = 192, conv 2 x 4 x (16 + 6) = 176
    assert flops.train_flops_per_token(SSM, 64) == 3 * (2 * 1184 + 2 * 368)
    # state 4x4x3 = 48 + conv window 3 x 22 = 66 -> 114 elements, read and
    # written in bf16, 2 layers, batch 2
    assert flops.decode_bytes(SSM, 2, 99) == \
        2 * 1184 + 2 * 2 * 2 * 2 * 114 + 2 * 2 * 32
