"""FLOP and byte counts against hand counts of the two configurations."""

import json
from pathlib import Path

import pytest

from bench.work import Sizes

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def sizes(name):
    return Sizes.of(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen3_8b_l18_weights_are_the_bytes_served_on_the_chip():
    s = sizes("qwen3-8b.l18")
    # parameter bytes the program's chip run reported for this cut
    assert s.param_bytes() == 9_437_496_320
    assert s.kv_bytes_per_token == 18 * 8 * 128 * 2 * 2 == 73_728


def test_qwen3_32b_l8_hand_counts():
    s = sizes("qwen3-32b.l8")
    layer = (5120 * (8192 + 2 * 1024) + 8192 * 5120 + 3 * 5120 * 25600
             + 2 * 5120 + 2 * 128)
    assert layer * 2 == 975_196_672                     # 975.2 MB a layer
    embed = 152064 * 5120 * 2
    assert embed == 1_557_135_360                       # 1.557 GB each
    assert s.param_bytes() == 8 * layer * 2 + 2 * embed + 5120 * 2
    assert s.param_bytes() == pytest.approx(10.92e9, rel=1e-3)
    assert s.kv_bytes_per_token == 32_768


def test_prefill_counts_real_tokens_and_half_the_causal_square():
    s = sizes("qwen3-8b.l18")
    p = 1000
    dense = 2 * 18 * s.layer_matmul_params * p
    attn = 4 * 18 * 32 * 128 * p * (p + 1) / 2
    assert s.prefill_flops([p]) == pytest.approx(dense + attn
                                                 + 2 * 4096 * 151936)
    assert s.prefill_flops([p, 24]) == pytest.approx(
        s.prefill_flops([p]) + s.prefill_flops([24]))


def test_decode_counts_resident_rows_and_valid_context():
    s = sizes("qwen3-8b.l18")
    one = s.decode_flops(1, 99)
    assert one == pytest.approx(2 * 18 * s.layer_matmul_params
                                + 2 * 4096 * 151936
                                + 4 * 18 * 32 * 128 * 100)
    # bytes: weights once per step whatever the rows, KV per valid token
    b1, b2 = s.decode_bytes(1, 1000), s.decode_bytes(2, 2000)
    assert b2 - b1 == pytest.approx(4096 * 2 + 1001 * 73_728)
    weights = s.decode_bytes(1, 0) - 73_728 - 4096 * 2
    assert weights < s.param_bytes()      # the embedding is read by rows


def test_shares_cannot_pass_the_peak_at_the_bound():
    # a step no faster than its bytes at the peak bandwidth reads <= 100%
    s = sizes("qwen3-32b.l8")
    nbytes = s.decode_bytes(32, 32 * 4000)
    assert 100.0 * nbytes / (nbytes / 819e9) / 819e9 == pytest.approx(100.0)
