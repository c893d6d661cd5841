import json
from pathlib import Path

import pytest

from bench import flops as F

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def tiny() -> dict:
    """Stem 2 -> 2x2x4; one K4S2 deconv 4 -> 3 (2x2 -> 4x4); a one-layer
    discriminator 3 -> 2 (4x4 -> 2x2) and its 8-wide head."""
    return {"z_dim": 2, "seed_hw": 2, "stem_ch": 4, "img_hw": 4, "img_ch": 3,
            "deconvs": [{"c_in": 4, "c_out": 3, "kernel": 4, "stride": 2, "padding": 1,
                         "output_padding": 0, "norm": "none", "act": "tanh"}],
            "disc_channels": [2], "disc_kernel": 4, "disc_stride": 2}


def test_generate_passes_hand_count():
    p = {q["layer"]: q for q in F.generate_passes(tiny(), images=3)}
    # stem: 2 x 16 MACs per image; deconv: 4 input pixels x 16 taps x 4 x 3
    assert p["stem"]["flops"] == 2 * 3 * (2 * 16)
    assert p["deconv0"]["flops"] == 2 * 3 * (4 * 16 * 4 * 3)
    assert p["deconv0"]["engine"] and not p["stem"]["engine"]
    # f32 bytes: per image 2x2x4 in, 4x4x3 out; weights 4x4x4x3 once
    assert p["deconv0"]["bytes"] == 4 * (3 * (16 + 48) + 192)


def test_train_step_passes_hand_count():
    passes = F.train_step_passes(tiny(), batch=2)
    by = {(q["layer"], q["pass"]): q["flops"] for q in passes}
    conv = 2 * 2 * (4 * 16 * 3 * 2)  # 2x2 outputs x 16 taps x 3 x 2, batch 2
    assert by[("conv0", "fwd")] == 2 * conv  # fake and real
    assert by[("conv0", "wgrad")] == 2 * conv
    assert by[("conv0", "dgrad")] == conv  # into the fake images only
    assert by[("head", "dgrad")] == 3 * 2 * 2 * 8  # fake twice, real once
    assert ("stem", "dgrad") not in by  # no gradient into the latent
    assert by[("deconv0", "dgrad")] == by[("deconv0", "fwd")]
    assert F.total_flops(passes) == sum(by.values())


def test_published_generator_macs():
    cfg = json.loads((Path(F.__file__).parent / "configs" / "dcgan.json").read_text())
    macs = sum(q["flops"] for q in F.generate_passes(cfg, 1) if q["engine"]) / 2
    assert macs == 638_976_000  # 3 x 209.7M + 9.8M: the scatter-sum count


def test_engine_bound_takes_larger_bound_per_pass():
    passes = [{"engine": True, "flops": 1000.0, "bytes": 10.0},   # 10 s by flops
              {"engine": True, "flops": 10.0, "bytes": 50.0},     # 5 s by bytes
              {"engine": False, "flops": 1e9, "bytes": 1e9}]
    t, bound = F.engine_bound(passes, PEAK)
    assert t == pytest.approx(15.0) and bound == "flops"
