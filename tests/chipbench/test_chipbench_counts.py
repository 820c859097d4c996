"""Operation and byte counts of the benchmark against hand-worked shapes."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import counts, peaks  # noqa: E402

OLMO = dict(layers=16, d_model=2048, heads=16, kv_heads=16, head_dim=128,
            d_ff=8192, vocab=50304)


def test_causal_pairs_halves_the_square():
    # 4x4 causal: 1 + 2 + 3 + 4 pairs.
    assert counts.causal_pairs(4, 4) == 10
    # A chunk of 2 queries after 2 cached keys sees 3 and 4 keys.
    assert counts.causal_pairs(2, 4, q_offset=2) == 7
    # Queries past the last key see every key.
    assert counts.causal_pairs(4, 4, q_offset=2) == 3 + 4 + 4 + 4
    n = 2048
    assert counts.causal_pairs(n, n) == n * (n + 1) // 2


def test_flash_fwd_counts_by_hand():
    # B=1, S=4, H=2, Hkv=1, d=8: 10 pairs x 2 heads x (QK 2d + PV 2d).
    flops, nbytes = counts.flash_fwd(1, 4, 4, 2, 1, 8)
    assert flops == 10 * 2 * 4 * 8
    # bf16: q and o (4 x 2 x 8 each) plus k and v of the one KV head (4 x 1 x 8 each).
    assert nbytes == 2 * (2 * 4 * 2 * 8 + 2 * 4 * 1 * 8)
    _, with_lse = counts.flash_fwd(1, 4, 4, 2, 1, 8, with_lse=True)
    assert with_lse == nbytes + 4 * 4 * 2


def test_gqa_reads_fewer_kv_bytes():
    _, mha = counts.flash_fwd(1, 1024, 1024, 32, 32, 128)
    _, gqa = counts.flash_fwd(1, 1024, 1024, 32, 4, 128)
    q_and_o = 2 * 2 * 1024 * 32 * 128
    assert mha - q_and_o == 8 * (gqa - q_and_o)


def test_flash_bwd_is_five_products():
    f_fwd, _ = counts.flash_fwd(2, 256, 256, 4, 2, 64)
    f_bwd, b_bwd = counts.flash_bwd(2, 256, 256, 4, 2, 64)
    assert f_bwd == f_fwd * 5 // 2
    q_side, kv_side = 2 * 256 * 4 * 64, 2 * 256 * 2 * 64
    assert b_bwd == 2 * (5 * q_side + 4 * kv_side) + 4 * 2 * 4 * 256


def test_olmo_training_flops_per_token():
    layers, head = counts.matmul_params(OLMO)
    assert layers == 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
    assert head == 50304 * 2048
    per_token = counts.train_flops(OLMO, 2048) / 2048
    assert abs(per_token - 7.4634e9) < 1e6


def test_roofline_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = counts.roofline_s(*counts.flash_fwd(1, 2048, 2048, 16, 16, 128), p)
    assert bound == "compute"
    t, bound = counts.roofline_s(1.0, 1e9, p)
    assert bound == "memory" and abs(t - 1e9 / 819e9) < 1e-15


def test_unknown_device_is_an_error():
    import pytest

    with pytest.raises(ValueError):
        peaks.peaks("cpu")


YI16 = dict(layers=16, d_model=4096, heads=32, kv_heads=4, head_dim=128, d_ff=11008,
            vocab=64000)


def test_prefill_counts_true_tokens_and_the_head_once():
    layers, head = counts.matmul_params(YI16)
    assert layers == 16 * (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008)
    assert head == 64000 * 4096
    n = 1536
    attn = 4 * 128 * 32 * 16 * n * (n + 1) // 2
    assert counts.prefill_flops(YI16, n) == 2 * layers * n + 2 * head + attn
    # About 5.74 GFLOP a prompt token at the median prompt.
    assert abs(counts.prefill_flops(YI16, n) / n - 5.738e9) < 0.001e9


def test_decode_counts_one_token_over_its_keys():
    layers, head = counts.matmul_params(YI16)
    assert counts.decode_flops(YI16, 1) == 2 * (layers + head) + 4 * 128 * 32 * 16
    step = counts.decode_flops(YI16, 2000) - counts.decode_flops(YI16, 1999)
    assert step == 4 * 128 * 32 * 16
