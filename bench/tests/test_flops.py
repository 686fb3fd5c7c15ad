"""Operation and byte counts against hand-computed values."""

import pytest

from core import flops

SMALL = {"num_layers": 2, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab_size": 10}


def test_flash_attention_counts_causal_pairs():
    # B1 H2 Kv1 S3 D4: pairs 6, QK^T and PV 2*2 ops per pair per dim
    f, b = flash = flops.flash_attention(1, 2, 1, 3, 4)
    assert f == 4 * 2 * 6 * 4 == 192
    # q and out 2*1*2*3*4, k and v 2*1*1*3*4, two bytes each
    assert b == 2 * (48 + 24) == 144
    assert flash == (192.0, 144)


def test_flash_decode_counts_valid_positions():
    f, b = flops.flash_decode(2, 4, 2, 5, 8)
    assert f == 4 * 2 * 4 * 5 * 8 == 1280
    # k, v: 2*2*2*5*8 = 320 elements; q, out: 2*2*4*8 = 128
    assert b == 2 * (320 + 128) == 896


def test_layer_weights():
    # q 8*4*2, k and v 8*2*2 each, o 4*2*8, three FFN matrices 8*16
    assert flops.layer_weights(SMALL) == 64 + 64 + 64 + 3 * 128 == 576


def test_prefill_and_decode_steps():
    att, _ = flops.flash_attention(3, 4, 2, 5, 2)
    want = 2 * 2 * 576 * 15 + 2 * att + 2 * 3 * 8 * 10
    assert flops.prefill(SMALL, 3, 5) == pytest.approx(want)
    dec, _ = flops.flash_decode(3, 4, 2, 7, 2)
    assert flops.decode(SMALL, 3, 7) == pytest.approx(
        2 * 2 * 576 * 3 + 2 * dec + 2 * 3 * 8 * 10)


def test_rounds_sum_prefill_and_growing_decode():
    got = flops.rounds(SMALL, [(3, 5, 2)])
    want = (flops.prefill(SMALL, 3, 5) + flops.decode(SMALL, 3, 6)
            + flops.decode(SMALL, 3, 7))
    assert got == pytest.approx(want)
