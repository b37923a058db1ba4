"""The yardstick's analytic counts against hand counts at the cells'
shapes."""
import json
from pathlib import Path

import pytest

from chipbench import counts
from chipbench.peaks import peaks_for

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["cifar10-ddpm", "cifar10-cld"])
def test_dit_eval_is_46_gflop_per_sample(name):
    f = counts.dit_eval_flops(cfg(name))
    d, S, L = 768, 256, 12
    # q, k, v, o and the 4x MLP: 12 d^2 multiply-adds per token per layer
    assert f["proj_mlp"] == 2 * S * 12 * d * d * L
    assert f["proj_mlp"] == pytest.approx(43.49e9, rel=1e-3)
    assert f["attention"] == pytest.approx(2.416e9, rel=1e-3)  # 4 S^2 d L
    # 6d and 2d adaLN projections of the time embedding, per sample
    assert f["adaln"] == 2 * d * 6 * d * L + 2 * d * 2 * d
    assert f["total"] == pytest.approx(46.0e9, rel=0.005)


def test_flash_attention_cost_at_the_backlog_shape():
    c = counts.flash_attention_cost(64, 256, 12, 64)
    assert c["flops"] == 4 * 64 * 12 * 256 * 256 * 64
    # q, k, v and o: 64 x 256 x 768 bf16 each
    assert c["bytes"] == 4 * 64 * 256 * 768 * 2
    least, bound = counts.roofline_seconds(c["flops"], c["bytes"],
                                           peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert least == pytest.approx(c["bytes"] / 819e9)


def test_round_fused_bytes_count_each_stream_once():
    B, K, Qb, kf, D = 64, 2, 2, 2, 3072
    c = counts.round_fused_cost(batch=B, K=K, Qb=Qb, kf=kf, D=D,
                                pool_rows=1)
    state, hist, eps = B * K * D, B * Qb * K * D, B * kf * D
    tables = B * (5 + 2 + (3 + Qb) * (kf * kf + 1))
    assert c["bytes"] == 4 * (2 * state + 2 * hist + eps + D + tables)
    corr = counts.round_fused_cost(batch=B, K=K, Qb=Qb, kf=kf, D=D,
                                   pool_rows=1, with_corrector=True)
    assert corr["bytes"] - c["bytes"] == 4 * (eps + B * Qb * (kf * kf + 1))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
