"""Collective bytes from optimized HLO text: an asynchronous collective
counts once, by the result of its ``-done``; a synchronous one by its
result, every array of a tuple included."""
import pytest

from bench import hlo

TEXT = """
  %collective-permute-start.40 = (bf16[16,1769472]{1,0:T(8,128)(2,1)}, bf16[16,1769472]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%reshape.1243), channel_id=1
  %collective-permute-done.40 = bf16[16,1769472]{1,0:T(8,128)(2,1)} collective-permute-done(%collective-permute-start.40), metadata={op_name="jit(train_step)/shard_map/ppermute"}
  %add_select_fusion.5 = bf16[16,1769472]{1,0} fusion(%reshape.1243, %collective-permute-done.40), kind=kLoop
  %all-reduce.2 = (f32[512,256]{1,0}, f32[256]{0}) all-reduce(%a, %b), to_apply=%sum
  %all-gather.1 = s8[4,1024]{1,0} all-gather(%c), dimensions={0}
"""


def test_collective_bytes():
    got = hlo.collective_bytes(TEXT)
    assert got["collective-permute"] == 16 * 1769472 * 2
    assert got["all-reduce"] == 512 * 256 * 4 + 256 * 4
    assert got["all-gather"] == 4 * 1024
    assert got["reduce-scatter"] == got["all-to-all"] == 0


def test_unknown_dtype_raises():
    with pytest.raises(ValueError):
        hlo.collective_bytes("  %all-reduce.1 = q7[8]{0} all-reduce(%x)")
