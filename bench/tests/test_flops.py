"""FLOP and byte counts against hand counts for both configurations."""

import json

import pytest

from bench import flops
from bench.harness import BENCH


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_deepseek_llm_7b_at_s512():
    cfg = _config("deepseek-llm-7b")
    # 4 x (wq + wk + wv + wo = 4 x 4096^2, + 3 x 4096 x 11008) + head 4096 x 25600
    assert flops.matmul_params(cfg) == 4 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 25600
    assert flops.matmul_params(cfg) == 914_358_272
    # attention: 4 layers x 2 x S x heads x head_dim = 16,777,216 per position
    assert flops.train_flops_per_position(cfg, 512) == 3 * (2 * 914_358_272 + 16_777_216)
    assert flops.train_flops_per_position(cfg, 512) == pytest.approx(5.536e9, rel=1e-3)
    # 16 slot rows of 640 lanes + idx and lens + three 16 x 512 grids, 4 bytes each
    assert flops.gather_bytes(16, 512) == 16 * 640 * 4 + 2 * 16 * 4 + 3 * 16 * 512 * 4
    assert flops.gather_bytes(16, 512) == 139_392


def test_phi3_medium_4k_at_s2048():
    cfg = _config("phi3-medium-4k")
    # per layer: wq, wo 5120^2 each; wk, wv 5120 x 1280 each (10 KV heads of 128);
    # three 5120 x 17920 MLP matrices
    layer = 2 * 5120 ** 2 + 2 * 5120 * 1280 + 3 * 5120 * 17920
    assert layer == 340_787_200
    assert flops.matmul_params(cfg) == 4 * layer + 5120 * 16032 == 1_445_232_640
    # attention: 4 x 2 x 2048 x 40 x 128 = 83,886,080 per position
    assert flops.train_flops_per_position(cfg, 2048) == 3 * (2 * 1_445_232_640 + 83_886_080)
    assert flops.train_flops_per_position(cfg, 2048) == pytest.approx(8.923e9, rel=1e-3)
    # S + 1 = 2049 rounds up to 17 lanes of 128 = 2176
    assert flops.gather_bytes(2, 2048) == 2 * 2176 * 4 + 2 * 2 * 4 + 3 * 2 * 2048 * 4 == 66_576
