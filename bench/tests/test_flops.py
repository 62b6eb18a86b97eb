"""The FLOP counter against counts made by hand from the published widths,
and a count and a CPU size for every configuration's family."""
import sys
import types

import pytest

from bench import flops, spec
from bench.reference import family


def _config(name):
    return spec.load_json(spec.find("configs", name))


def test_mamba2_130m_by_hand():
    # per token and layer, multiply-adds: in_proj 768 x 3352, out_proj
    # 1536 x 768, conv 4 x 1792, SSD (257/2) x 128 + (257/2) x 24 x 64
    # + 2 x 24 x 64 x 128; then the tied head 768 x 50288
    layer = 2574336 + 1179648 + 7168 + 16448 + 197376 + 393216
    per_token = 24 * layer + 768 * 50288
    assert per_token == 143457792
    c = _config("mamba2-130m")
    assert flops.macs_per_token(c, 2048) == per_token
    assert flops.train_flops_per_step(c, 8, 2048) == 6 * per_token * 8 * 2048


def test_glm4_stage_by_hand():
    # per token and layer: q, k, v 4096 x 36 x 128, o 4096 x 4096, causal
    # scores and values 2 x 32 x 128 x 2049 / 2, swiglu 3 x 4096 x 13696;
    # then the head 4096 x 18944
    layer = 18874368 + 16777216 + 8392704 + 168296448
    per_token = 3 * layer + 4096 * 18944
    c = _config("glm4-9b-3l")
    assert flops.macs_per_token(c, 2048) == per_token
    assert flops.train_flops_per_step(c, 4, 2048) == pytest.approx(
        3.5124846526464e13)


def test_unknown_family_is_an_error(monkeypatch):
    """A family module without ``macs_per_token`` raises, naming itself."""
    monkeypatch.setitem(sys.modules, "bench.reference.uncounted",
                        types.ModuleType("bench.reference.uncounted"))
    with pytest.raises(ValueError, match="uncounted"):
        flops.macs_per_token({"reference": "uncounted", "model": {}}, 128)


@pytest.mark.parametrize("name", [c["name"] for c in spec.benchmark()
                                  ["configs"]])
def test_every_configuration_has_a_count_and_a_cpu_size(name):
    c = _config(name)
    assert flops.macs_per_token(c, 2048) > 0
    tiny = family(c["reference"]).TINY
    assert flops.macs_per_token(dict(c, model=tiny), 128) > 0


def test_dense_family_refuses_other_activations():
    """The dense count and reference are SwiGLU's: another activation is an
    error, not a SwiGLU count."""
    c = _config("glm4-9b-3l")
    with pytest.raises(ValueError, match="gelu"):
        flops.macs_per_token(dict(c, model=dict(c["model"],
                                                activation="gelu")), 2048)
