"""Roofline machinery unit tests: HLO collective parsing + term math."""
import pytest

from repro.launch import roofline as RL


HLO_SAMPLE = """
  %x = f32[16,4096]{1,0} parameter(0)
  %ag = bf16[32,128]{1,0} all-gather(%y), replica_groups=[16,16]<=[256]
  %ar = (f32[8,8]{1,0}, f32[4]{0}) all-reduce(%a, %b), channel_id=3
  %ags = f32[64]{0} all-gather-start(%z), channel_id=9
  %agd = f32[64]{0} all-gather-done(%ags)
  %a2a = s32[128,4]{1,0} all-to-all(%w), channel_id=11
  %cp = u32[2,2]{1,0} collective-permute(%v), channel_id=12
  %dot = f32[16,16]{1,0} dot(%x, %x)
"""


def test_shape_bytes():
    assert RL.shape_bytes("f32[16,4096]") == 16 * 4096 * 4
    assert RL.shape_bytes("bf16[32,128]") == 32 * 128 * 2
    assert RL.shape_bytes("(f32[8,8], f32[4])") == (64 + 4) * 4
    assert RL.shape_bytes("pred[]") == 1


def test_collective_bytes_parsing():
    out = RL.collective_bytes(HLO_SAMPLE)
    assert out["counts"] == {"all-gather": 2, "all-reduce": 1,
                             "all-to-all": 1, "collective-permute": 1}
    assert out["bytes"]["all-gather"] == 32 * 128 * 2 + 64 * 4  # -done skipped
    assert out["bytes"]["all-reduce"] == (64 + 4) * 4
    assert out["bytes"]["all-to-all"] == 128 * 4 * 4
    assert out["total_bytes"] == sum(out["bytes"].values())


def test_roofline_terms_math():
    cost = {"flops": 197e12, "bytes accessed": 819e9}
    coll = {"total_bytes": 50e9}
    t = RL.roofline_terms(cost, coll, n_chips=256, model_flops=197e12 * 256,
                          device_kind="TPU v5 lite")
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 1.0) < 1e-9
    assert abs(t["collective_s"] - 1.0) < 1e-9
    assert abs(t["useful_flops_ratio"] - 1.0) < 1e-9
    # analytic memory overrides dominance
    t2 = RL.roofline_terms(cost, coll, 256, memory_bytes_analytic=819e9 * 10,
                           device_kind="TPU v5 lite")
    assert t2["dominant"] == "memory"
    # a chip without published peaks is an error, not a default
    with pytest.raises(KeyError):
        RL.roofline_terms(cost, coll, 256, device_kind="TPU v99")


def test_lm_model_flops_conventions():
    from repro.configs.registry import get_arch
    cfg = get_arch("qwen3-32b").config
    train = get_arch("qwen3-32b").shape("train_4k")
    dec = get_arch("qwen3-32b").shape("decode_32k")
    f_train = RL.lm_model_flops(cfg, train)
    f_dec = RL.lm_model_flops(cfg, dec)
    n = cfg.active_param_count()
    assert f_train == 6.0 * n * 256 * 4096
    assert f_dec == 2.0 * n * 128
