"""The peak table and the arithmetic of rooflines and utilisation."""
import json

import pytest

from perfbench.lib import roofline
from perfbench.lib.harness import BENCH_DIR


def smollm():
    return json.loads((BENCH_DIR / "configs" / "smollm-135m.json").read_text())


def test_v5e_peaks_and_unknown_device():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_dog_cost_on_a_tile():
    c = roofline.dog_cost((512, 512, 16), (1, 1, 0.5), (3, 3, 1.5), 4)
    n = 512 * 512 * 16
    assert c.flops == 6 * 2 * 9 * n + n
    assert c.bytes == 8 * n
    p = roofline.peaks("TPU v5 lite")
    assert c.bound(p) == "memory"
    # 33.5 MB at 819 GB/s is 40.96 us; measured in 409.6 us that is 10%
    assert roofline.roofline_pct(c, 10 * 8 * n / 819e9, p) == pytest.approx(10.0)


def test_smollm_flops_per_token():
    cfg = smollm()
    assert roofline.llama_matmul_params(cfg) == 134_479_872
    attn = 30 * 4 * 9 * 64 * (2049 / 2)
    assert roofline.llama_train_flops_per_token(cfg, 2048) == pytest.approx(
        6 * 134_479_872 + 3 * attn)


def test_mfu_and_roofline_arithmetic():
    p = roofline.peaks("TPU v5 lite")
    assert roofline.mfu_pct(1e9, 197e3 / 2, p) == pytest.approx(50.0)
    assert roofline.mfu_pct(1e9, 197e3, p, chips=4) == pytest.approx(25.0)
    with pytest.raises(ValueError):
        roofline.roofline_pct(roofline.Cost(1, 1), 0.0, p)
