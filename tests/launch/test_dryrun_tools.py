"""Unit tests for the dry-run analysis tooling (pure functions — the full
lower+compile path is exercised by the sweep logs in experiments/)."""
import jax
import pytest

from repro.launch.analysis import (INPUT_SHAPES, model_flops_per_step,
                                   parse_collective_bytes)
from repro.models import get_config


HLO_SAMPLE = """
  %all-reduce.5 = bf16[1024,512]{1,0} all-reduce(bf16[1024,512]{1,0} %x), replica_groups={}
  %all-gather.2 = f32[64,128]{1,0} all-gather(f32[8,128]{1,0} %y), dimensions={0}
  %rs = f32[8,128]{1,0} reduce-scatter(f32[64,128]{1,0} %z), dimensions={0}
  %a2a = (s32[4,2]{1,0}) all-to-all(s32[4,2]{1,0} %w)
  %cp.1 = u8[16]{0} collective-permute(u8[16]{0} %v)
  %dot.3 = bf16[10,10]{1,0} dot(bf16[10,10]{1,0} %a, bf16[10,10]{1,0} %b)
  %ars = bf16[2,2]{1,0} all-reduce-start(bf16[2,2]{1,0} %q)
"""


def test_parse_collective_bytes_categories():
    r = parse_collective_bytes(HLO_SAMPLE)
    ops = r["per_op_bytes"]
    assert ops["all-reduce"] == 1024 * 512 * 2 + 2 * 2 * 2  # incl. -start
    assert ops["all-gather"] == 64 * 128 * 4
    assert ops["reduce-scatter"] == 8 * 128 * 4
    assert ops["all-to-all"] == 4 * 2 * 4
    assert ops["collective-permute"] == 16
    # all-reduce weighted 2x in the link-byte total
    want = 2 * ops["all-reduce"] + ops["all-gather"] + \
        ops["reduce-scatter"] + ops["all-to-all"] + ops["collective-permute"]
    assert r["total_link_bytes"] == want
    assert r["per_op_count"]["all-reduce"] == 2


def test_parse_sums_every_element_of_a_tuple_collective():
    """XLA's combiner merges gradient all-reduces into one tuple-shaped op:
    every element counts, bytes and arrays alike."""
    hlo = ("  %all-reduce.39 = (f32[512,256]{1,0}, f32[256]{0}, /*index=2*/"
           "bf16[2,64,256]{2,1,0}) all-reduce(f32[512,256]{1,0} %a, "
           "f32[256]{0} %b, bf16[2,64,256]{2,1,0} %c), to_apply=%add\n")
    r = parse_collective_bytes(hlo)
    assert r["per_op_bytes"]["all-reduce"] == (512 * 256 * 4 + 256 * 4
                                              + 2 * 64 * 256 * 2)
    assert r["per_op_count"]["all-reduce"] == 3
    assert r["unknown_dtypes"] == {}


def test_parse_ignores_non_collectives():
    r = parse_collective_bytes("%dot = f32[8,8]{1,0} dot(...)\n")
    assert r["total_link_bytes"] == 0


def test_parse_known_dtypes_report_no_unknowns():
    assert parse_collective_bytes(HLO_SAMPLE)["unknown_dtypes"] == {}


def test_parse_unknown_dtype_warns_once_and_is_surfaced():
    """An HLO dtype we have no byte width for must not be silently assumed
    4 B: it is tallied in ``unknown_dtypes`` and warned about once."""
    import warnings

    from repro.launch import analysis

    hlo = "  %ar = f4e2m1fn[64]{0} all-reduce(f4e2m1fn[64]{0} %x)\n" * 3
    analysis._WARNED_DTYPES.discard("f4e2m1fn")  # isolate from other tests
    with pytest.warns(RuntimeWarning, match="f4e2m1fn"):
        r = parse_collective_bytes(hlo)
    assert r["unknown_dtypes"] == {"f4e2m1fn": 3}
    assert r["per_op_bytes"]["all-reduce"] == 3 * 64 * 4  # 4 B fallback
    assert r["per_op_count"]["all-reduce"] == 3
    # warn-once: a second parse of the same dtype stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        r2 = parse_collective_bytes(hlo)
    assert r2["unknown_dtypes"] == {"f4e2m1fn": 3}


def test_input_shapes_match_assignment():
    assert INPUT_SHAPES["train_4k"] == dict(kind="train", seq_len=4096,
                                            global_batch=256)
    assert INPUT_SHAPES["prefill_32k"] == dict(kind="prefill", seq_len=32768,
                                               global_batch=32)
    assert INPUT_SHAPES["decode_32k"] == dict(kind="decode", seq_len=32768,
                                              global_batch=128)
    assert INPUT_SHAPES["long_500k"] == dict(kind="decode", seq_len=524288,
                                             global_batch=1)


def test_model_flops_scaling():
    cfg = get_config("llama3.2-1b")
    t = model_flops_per_step(cfg, "train", 4096, 256)
    p = model_flops_per_step(cfg, "prefill", 4096, 256)
    d = model_flops_per_step(cfg, "decode", 4096, 256)
    assert abs(t / p - 3.0) < 1e-9        # 6ND vs 2ND
    assert d == p / 4096                  # one token per sequence
    # MoE: active < total params
    moe = get_config("deepseek-moe-16b")
    assert moe.active_param_count() < moe.param_count()
    ratio = moe.active_param_count() / moe.param_count()
    assert 0.1 < ratio < 0.6              # 6+shared of 64 experts active


def test_param_count_orders_of_magnitude():
    """Sanity: parameter-count estimates land near the published sizes."""
    expect = {
        "llama3.2-1b": (1.0e9, 2.0e9),
        "qwen2-7b": (6e9, 9e9),
        "glm4-9b": (8e9, 12e9),
        "nemotron-4-340b": (3.0e11, 3.8e11),
        "deepseek-moe-16b": (1.4e10, 2.1e10),
        "mamba2-130m": (1.0e8, 2.2e8),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_long500k_eligibility():
    assert not get_config("whisper-large-v3").supports_long_decode()
    for a in ("jamba-v0.1-52b", "mamba2-130m", "llama3.2-1b", "qwen2-vl-2b"):
        assert get_config(a).supports_long_decode()
    # dense archs get the sliding-window variant
    v = get_config("qwen2-7b").long_context_variant(8192)
    assert v.sliding_window == 8192
    # SSM/hybrid run natively — no variant
    assert get_config("mamba2-130m").long_context_variant(8192).sliding_window == 0
