"""Work counts and the peaks table, against counts made by hand."""
import itertools

import pytest

from bench import peaks
from bench.work import decode_attention, flash_attention, model, ssd_scan


def _pairs_by_loop(s, window):
    return sum(1 for i, j in itertools.product(range(s), repeat=2)
               if j <= i and (window is None or i - j < window))


@pytest.mark.parametrize("s,window", [(8, None), (8, 3), (8, 8), (8, 20), (33, 5)])
def test_visible_pairs_matches_a_loop(s, window):
    assert flash_attention.visible_pairs(s, window) == _pairs_by_loop(s, window)


def test_flash_work_by_hand():
    # B=1, S=8, H=2, KV=1, d=4: 36 causal pairs, 2 products of 2*d FLOPs each
    assert flash_attention.work(1, 8, 2, 1, 4, None) == (4 * 2 * 4 * 36, 2 * 8 * 4 * 6)
    # a window of 3 (shorter than the sequence): 1 + 2 + 3 + 5 * 3 = 21 pairs
    flops, moved = flash_attention.work(1, 8, 2, 1, 4, 3)
    assert flops == 4 * 2 * 4 * 21 == 672
    assert moved == 384


def test_decode_work_by_hand():
    # B=2, 5 live entries, H=4, KV=2, d=8, bf16
    flops, moved = decode_attention.work(2, 5, 4, 2, 8)
    assert flops == 4 * 2 * 4 * 8 * 5 == 1280
    assert moved == 2 * 2 * 8 * (2 * 5 * 2 + 2 * 4) == 896


def test_live_entries_follow_the_window():
    assert decode_attention.live_entries(4, 4, None) == [5, 6, 7]
    assert decode_attention.live_entries(4, 4, 5) == [5, 5, 5]
    # danube-serve-long: 4096-token prompts fill the 4096 window
    assert set(decode_attention.live_entries(4096, 32, 4096)) == {4096}


def test_ssd_work_by_hand():
    # B=1, S=4, H=1, P=2, G=1, N=3, chunk 2: two chunks, each
    # 2*L*L*(N+P) + 4*L*N*P = 40 + 48 FLOPs
    flops, moved = ssd_scan.work(1, 4, 1, 2, 1, 3, 2)
    assert flops == 2 * (2 * 2 * 2 * 5 + 4 * 2 * 3 * 2) == 176
    assert moved == 2 * 4 * (2 * 2 + 2 * 3) + 4 * 4 + 4 * 2 * 3


def test_model_flops_count_the_danube_prefill():
    a = {"d_model": 2560, "n_layers": 24, "n_heads": 32, "n_kv_heads": 8,
         "head_dim": 80, "d_ff": 6912, "vocab_size": 32000, "window": 4096}
    body = 24 * (2560 * 80 * 80 + 3 * 2560 * 6912)
    assert model.body_weights(a) == body
    attn = 24 * 4.0 * 8 * 32 * 80 * (4096 * 4097 // 2)
    assert model.prefill_flops(a, 8, 4096) == 2.0 * body * 8 * 4096 + attn + 2.0 * 2560 * 32000 * 8


def test_peaks_know_the_v5e_and_nothing_else():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
