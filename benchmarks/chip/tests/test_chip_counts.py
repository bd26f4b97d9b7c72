"""Needed-work counts, through each config's family, against hand-worked
numbers for one layer of each config, and the peak table."""

import json
from pathlib import Path

import pytest

from benchmarks.chip.cell import load_family
from benchmarks.chip.counts import prompt_chunks
from benchmarks.chip.peaks import peaks_for

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def counted(name, **changed):
    config = json.loads((CONFIGS / f"{name}.json").read_text())
    return load_family(config["family"]).counts(
        dict(config["model"], **changed))


def one_layer(name):
    return counted(name, num_hidden_layers=1)


def test_olmo_1b_one_layer():
    m = one_layer("olmo-1b")
    # q, k, v, o: 4 x 2048 x (16 x 128); gated MLP: 3 x 2048 x 8192
    assert m.layer_matmul_params() == 16_777_216 + 50_331_648
    assert m.linear_flops_per_token() == 134_217_728
    # MHA: K and V of 16 heads x 128 in bf16
    assert m.kv_bytes_per_position() == 8192
    # the query at position 999 sees 1000 keys: 2 matmuls x 2 FLOPs
    assert m.attn_flops(999, 1) == 4 * 16 * 128 * 1000 == 8_192_000
    # non-parametric norms: weights are the layer plus the tied head
    assert m.decode_weight_bytes() == (67_108_864 + 50304 * 2048) * 2


def test_yi_6b_one_layer():
    m = one_layer("yi-6b")
    # q, o: 2 x 4096 x (32 x 128); k, v: 2 x 4096 x (4 x 128)
    assert m.layer_matmul_params() == 33_554_432 + 4_194_304 + 135_266_304
    assert m.kv_bytes_per_position() == 2048       # GQA: 4 KV heads
    # RMSNorm scales: two per layer and the final one
    assert m.decode_weight_bytes() == (173_015_040 + 8192 + 4096
                                       + 64000 * 4096) * 2


def test_whole_models():
    olmo, yi = counted("olmo-1b"), counted("yi-6b")
    assert olmo.decode_weight_bytes() == 2_353_528_832     # 2.35 GB
    assert yi.decode_weight_bytes() == 6_061_039_616       # 6.06 GB
    assert olmo.kv_bytes_per_position() == 128 * 1024
    assert yi.kv_bytes_per_position() == 32 * 1024


def test_decode_step_bytes_count_live_positions_only():
    m = one_layer("olmo-1b")
    w, kv = m.decode_weight_bytes(), m.kv_bytes_per_position()
    # two live rows at fills 10 and 0: 11 + 1 positions read, 2 written
    assert m.decode_step_bytes([10, 0]) == w + 2 * 2048 * 2 + 12 * kv + 2 * kv
    assert m.decode_step_bytes([]) == w
    q_out = 2 * 16 * 128 * 2
    assert m.flash_decode_bytes([10, 0]) == 2 * q_out + 12 * kv


def test_prefill_counts_follow_the_chunks():
    m = one_layer("yi-6b")
    assert prompt_chunks(300, 128) == [(0, 128), (128, 128), (256, 44)]
    assert prompt_chunks(0, 128) == []
    flops, nbytes = m.flash_prefill_work(prompt_chunks(300, 128))
    # chunking does not change the causal work of the prompt
    assert flops == m.attn_flops(0, 300) == 4 * 32 * 128 * (300 * 301 // 2)
    q_out = 2 * 32 * 128 * 2
    assert nbytes == 300 * q_out + (128 + 256 + 300) * 2048
    assert m.prefill_flops(300) == (300 * m.linear_flops_per_token()
                                    + m.attn_flops(0, 300))
    assert m.decode_flops(300) == (m.linear_flops_per_token()
                                   + 2 * 4096 * 64000 + m.attn_flops(300, 1))


def test_peak_table():
    p = peaks_for("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")
