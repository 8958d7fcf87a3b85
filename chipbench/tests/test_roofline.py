"""Operation and byte counts from shapes, and the table of peaks."""

from __future__ import annotations

import json

import pytest

from chipbench import roofline
from chipbench.weights import Dims

QWEN2 = Dims(layers=28, d=1536, heads=12, kv_heads=2, head_dim=128,
             ff=8960, vocab=151936, tied=True, qkv_bias=True, qk_norm=False,
             rope_theta=1e6, eps=1e-6)
PEAKS = roofline.peaks("TPU v5 lite")

# qwen2-1.5b's seven projections, (K, N), at M = 16 decode rows
SEVEN = {"q_proj": (1536, 1536), "k_proj": (1536, 256),
         "v_proj": (1536, 256), "o_proj": (1536, 1536),
         "gate_proj": (1536, 8960), "up_proj": (1536, 8960),
         "down_proj": (8960, 1536)}


def test_seven_projection_shapes():
    assert QWEN2.projections() == SEVEN


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_projection_ops_and_bytes(name):
    k, n = SEVEN[name]
    ops, nbytes = roofline.projection(16, k, n)
    assert ops == 2 * 16 * k * n
    assert nbytes == 16 * k + k * n + 4 * 16 * n
    t, bound = roofline.least_time(ops, nbytes, PEAKS)
    # 16 rows of int8: far below the ridge (393e12 / 819e9 = 480 ops/B)
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


def test_gate_projection_numbers():
    ops, nbytes = roofline.projection(16, 1536, 8960)
    assert ops == 440_401_920
    assert nbytes == 14_360_576


def test_a_prefill_block_is_bound_by_operations():
    ops, nbytes = roofline.projection(8192, 5120, 25600)
    assert roofline.least_time(ops, nbytes, PEAKS)[1] == "ops"


def test_token_and_prefill_ops_agree():
    w = sum(k * n for k, n in SEVEN.values()) * 28
    assert roofline.weights_per_token(QWEN2, False) == w
    assert roofline.weights_per_token(QWEN2, True) == w + 1536 * 151936
    assert roofline.prefill_ops(QWEN2, 9) == pytest.approx(
        sum(roofline.token_ops(QWEN2, t + 1, False) for t in range(9)))


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v4")


def test_peaks_name_their_source():
    table = json.loads(roofline.PEAKS_FILE.read_text())
    assert all(v["source"] for v in table.values())
