"""The peaks table and the operation and byte counts, against counts
made by hand at small sizes."""
import json
from pathlib import Path

import pytest

from bench.harness import cell, flops
from bench.harness.model import Dims

ROOT = Path(__file__).resolve().parents[2]
D = Dims(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
         d_ff=16, vocab=10, rope_theta=1e4, norm_eps=1e-5, qk_norm=False,
         tied=False)
PEAK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_peaks_keyed_by_device_kind():
    table = json.loads((ROOT / "bench/peaks.json").read_text())["devices"]
    assert cell._peaks(ROOT, "TPU v5 lite") == table["TPU v5 lite"]
    assert table["TPU v5 lite"]["flops_per_s"] == 197e12
    assert table["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cell.SetupError):
        cell._peaks(ROOT, "cpu")


def test_dense_params():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16; head 8x10
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert flops.dense_params(D) == 2 * per_layer + 80
    assert flops.dense_params(D, head=False) == 2 * per_layer


def test_lora_and_attention():
    # rank 2 over q (8+8), k (8+4), v (8+4), o (8+8) = 2 * 56, x2 per MAC
    assert flops.lora_flops_per_token(D, 2) == 2 * 2 * 2 * 56
    # QK and PV: 2 * 2 ops x heads x head_dim x keys x layers
    assert flops.attn_flops(D, 5) == 4 * 4 * 2 * 5 * 2


def test_prefill_counts_causal_keys_and_one_head():
    # 3 suffix tokens after a 4-token prefix see 5, 6, 7 keys
    f = flops.prefill_flops(D, 4, 3, 2)
    want = (2 * flops.dense_params(D, head=False) * 3
            + flops.lora_flops_per_token(D, 2) * 3
            + flops.attn_flops(D, 1) * (5 + 6 + 7) + 2 * 8 * 10)
    assert f == want


def test_decode_counts():
    assert flops.decode_flops(D, 9, 4) == (
        2 * flops.dense_params(D) + flops.lora_flops_per_token(D, 4)
        + flops.attn_flops(D, 9))


def test_paged_attention_reads_pages_up_to_length():
    f, b = flops.paged_attention_cost(D, 10)
    assert f == flops.attn_flops(D, 10)
    assert b == 2 * 2 * 10 * 4 * 4 + 2 * 2 * 8 * 4


def test_bgmv_counts_true_rank():
    f, b = flops.bgmv_cost(D, 3)
    assert f == 2 * 2 * 3 * 56
    assert b == 2 * (3 * 56 * 2 + 56 * 4)


def test_roofline_picks_the_larger_bound():
    assert flops.roofline_seconds(1000, 10, PEAK) == (10.0, "compute")
    assert flops.roofline_seconds(10, 1000, PEAK) == (100.0, "bandwidth")
