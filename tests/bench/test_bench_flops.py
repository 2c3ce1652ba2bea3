"""FLOP and byte counts against a hand count at a toy shape."""
import pytest

from bench import flops, peaks

# L=2, d=8, f=16, 4 query heads, 2 KV heads, head_dim 2, vocab 10
S = {"L": 2, "d": 8, "f": 16, "hq": 4, "hkv": 2, "hd": 2, "V": 10,
     "bias": True}


def test_matmul_and_head():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up/down 3 x 8x16
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert flops.matmul_flops_per_token(S) == 2 * 2 * per_layer
    assert flops.head_flops(S) == 2 * 8 * 10


def test_attention_counts_needed_pairs():
    assert flops.chunk_keys(0, 3) == 1 + 2 + 3
    assert flops.chunk_keys(5, 2) == 6 + 7
    # 2 layers x 4 heads x hd 2 x (QK + PV: 2 + 2 flops per pair)
    assert flops.attention_flops(S, 13) == 2 * 4 * 2 * 4 * 13


def test_prefill_and_decode():
    rows = [(0, 3, False), (5, 2, True)]
    want = (5 * flops.matmul_flops_per_token(S)
            + flops.attention_flops(S, 6 + 13) + flops.head_flops(S))
    assert flops.prefill_flops(S, rows) == want
    ctx = [4, 9]
    assert flops.decode_flops(S, ctx) == pytest.approx(
        2 * (flops.matmul_flops_per_token(S) + flops.head_flops(S))
        + flops.attention_flops(S, 13))


def test_paged_attention_bytes():
    f, b = flops.paged_attention_work(S, [4, 9], itemsize=2)
    assert f == flops.attention_flops(S, 13)
    # per layer: K+V of 13 positions x 2 KV heads x hd 2 x 2 bytes, plus
    # q and out of 2 rows x 4 heads x hd 2 x 2 bytes
    assert b == 2 * (2 * 13 * 2 * 2 * 2 + 2 * 2 * 4 * 2 * 2)


def test_peaks_refuse_an_unknown_device():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["int8_ops"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
