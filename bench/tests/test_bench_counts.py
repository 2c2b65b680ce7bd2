"""The yardstick's counts against hand counts, and the peaks table."""
import pytest

from bench import counts, harness, peaks


def _cfg(name):
    return harness.load_json(f"{harness.BENCH}/configs/{name}.json")


def test_round_flops_stablelm_hand_count():
    # Per layer: Q, K, V, O 2048x2048 each and the SwiGLU MLP 3 x 2048x5632.
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    dense = 2 * (24 * per_layer + 2048 * 100352)       # + the tied head
    attn = 24 * 2 * 2 * 96 * 2048                       # QK^T and PV at context 96
    lora = 24 * 2 * (2 * 8 * (2048 + 2048))            # Q and V adapters
    per_token = (dense + attn + lora) + (dense + 2 * attn + 2 * lora)
    hand = 8 * 2 * 4 * 96 * per_token                   # clients x steps x tokens
    assert hand == 35_762_045_190_144
    traffic = harness.load_json(f"{harness.BENCH}/traffic/round-c8.json")
    assert counts.round_flops(_cfg("stablelm-1.6b"), traffic) == hand


def test_agg_flops_and_bytes_deepseek_hand_count():
    # Module vec dims of one layer: Q.A 8192x8, Q.B 8x8192, V.A 8192x8, V.B 8x1024.
    per_layer = 3 * 65536 + 8192
    vec = 95 * per_layer
    assert vec == 19_456_000
    cfg = _cfg("deepseek-67b")
    assert counts.agg_flops(cfg, 20, 30) == 4 * vec * 20 * 20 * 30 == 933_888_000_000
    assert counts.agg_least_bytes(cfg, 20, 30) == 5 * vec * 20 * 4 * 30 == 233_472_000_000


def test_roofline_bound_names_the_limit():
    p = peaks.peaks("TPU v5 lite")
    t, bound = counts.roofline_time(933_888_000_000, 233_472_000_000, p, 1)
    assert bound == "hbm" and t == pytest.approx(233_472_000_000 / 819e9)
    t4, _ = counts.roofline_time(933_888_000_000, 233_472_000_000, p, 4)
    assert t4 == pytest.approx(t / 4)


def test_peaks_v5e():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bytes_per_s"] * 8 == 1600e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5e"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)
