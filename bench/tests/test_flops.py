"""The FLOP counter against counts made by hand from the published widths."""
import pytest

from bench import flops, spec


def _model(name):
    return spec.load_json(spec.find("configs", name))["model"]


def test_mamba2_130m_by_hand():
    # per token and layer, multiply-adds: in_proj 768 x 3352, out_proj
    # 1536 x 768, conv 4 x 1792, SSD (257/2) x 128 + (257/2) x 24 x 64
    # + 2 x 24 x 64 x 128; then the tied head 768 x 50288
    layer = 2574336 + 1179648 + 7168 + 16448 + 197376 + 393216
    per_token = 24 * layer + 768 * 50288
    assert per_token == 143457792
    m = _model("mamba2-130m")
    assert flops.macs_per_token(m, 2048) == per_token
    assert flops.train_flops_per_step(m, 8, 2048) == 6 * per_token * 8 * 2048


def test_glm4_stage_by_hand():
    # per token and layer: q, k, v 4096 x 36 x 128, o 4096 x 4096, causal
    # scores and values 2 x 32 x 128 x 2049 / 2, swiglu 3 x 4096 x 13696;
    # then the head 4096 x 18944
    layer = 18874368 + 16777216 + 8392704 + 168296448
    per_token = 3 * layer + 4096 * 18944
    m = _model("glm4-9b-3l")
    assert flops.macs_per_token(m, 2048) == per_token
    assert flops.train_flops_per_step(m, 4, 2048) == pytest.approx(
        3.5124846526464e13)


def test_unknown_family_is_an_error():
    with pytest.raises(ValueError):
        flops.macs_per_token({"arch_type": "moe"}, 128)
