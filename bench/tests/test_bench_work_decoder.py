"""Decoder work counts against hand counts at a small shape, and at the
published StarCoder2-3B widths against its published parameter count."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import work_decoder as wd  # noqa: E402

SMALL = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab_size": 10, "window": 6}


def test_layer_and_model_parameters_by_hand():
    # q 8*4*2=64, k and v 8*2*2=32 each, o 4*2*8=64; biases 8+4+4+8=24;
    # mlp 8*16*2=256 + 16 + 8; two norms 2*2*8=32
    assert wd.layer_params(SMALL) == 64 + 32 + 32 + 64 + 24 + 280 + 32
    body, embed = wd.params(SMALL)
    assert body == 2 * 528 + 16 and embed == 80


def test_decode_work_by_hand():
    ops, nbytes = wd.decode_work(SMALL, batch=3, slots=5)
    # weights: 2 ops per weight and sequence; head 2*8*10 per sequence;
    # attention 4 * layers * heads * head_dim * slots per sequence
    assert ops == 2 * 1072 * 3 + 2 * 8 * 10 * 3 + 4 * 2 * 4 * 2 * 5 * 3
    # every weight once (bf16) + k and v of 2 layers x 3 seqs x 5 slots x
    # 2 heads x 2 dims (bf16)
    assert nbytes == (1072 + 80) * 2 + 2 * 2 * 3 * 5 * 2 * 2 * 2


def test_cache_slots_are_the_window_or_the_positions():
    assert wd.cache_slots(SMALL, 100) == 6
    assert wd.cache_slots(SMALL, 4) == 4
    assert wd.cache_slots(dict(SMALL, window=0), 100) == 100


def test_published_starcoder2_3b():
    dec = {"n_layers": 30, "d_model": 3072, "n_heads": 24, "n_kv_heads": 2,
           "head_dim": 128, "d_ff": 12288, "vocab_size": 49152,
           "window": 4096}
    body, embed = wd.params(dec)
    assert body + embed == pytest.approx(3.03e9, rel=0.01)
    ops, nbytes = wd.decode_work(dec, batch=16, slots=4096)
    assert nbytes == pytest.approx(6.06e9 + 2.01e9, rel=0.01)
