"""work.py against counts made by hand at one shape, and the peaks
table's refusal of an unknown device."""

import pytest

import _paths  # noqa: F401
import work

# qwen2-0.5b: 24 layers, d 896, 14 heads, 2 kv heads of 64, ff 4864
S = work.Shape(layers=24, d_model=896, heads=14, kv_heads=2, head_dim=64,
               d_ff=4864, vocab=151936)


def test_paged_attention_by_hand():
    flops, nbytes = work.paged_attention(S, [100, 300])
    # QK and PV: 2 flops per multiply-add, 14 heads x 64 x 400 keys each
    assert flops == 2 * 2 * 14 * 64 * 400
    # K and V of 400 live tokens, 2 heads x 64 x 2 bytes, plus q and out
    assert nbytes == 2 * 400 * 2 * 64 * 2 + 2 * 2 * 14 * 64 * 2


def test_flash_prefill_by_hand():
    flops, nbytes = work.flash_prefill(S, 128, 256)
    keys = sum(i + 1 for i in range(128, 256))
    assert flops == 4 * 14 * 64 * keys
    assert nbytes == 2 * 256 * 2 * 64 * 2 + 2 * 128 * 14 * 64 * 2


def test_step_flops_by_hand():
    per_layer = 2 * 896 * 14 * 64 + 2 * 896 * 2 * 64 + 3 * 896 * 4864
    assert S.layer_matmul_params == per_layer
    head = 2 * 896 * 151936
    one = work.step_flops(S, [10])
    assert one == 2 * 24 * per_layer + head + 24 * 4 * 14 * 64 * 10
    with_chunk = work.step_flops(S, [10], (0, 128))
    assert with_chunk - one == (128 * 2 * 24 * per_layer + head
                                + 24 * work.flash_prefill(S, 0, 128)[0])


def test_roofline_and_peaks():
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert work.least_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 819e9, peak) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
