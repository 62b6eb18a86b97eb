"""Per-kernel interpret-mode validation against the pure-jnp oracles,
sweeping shapes and dtypes (on the CPU the kernels run in the Pallas
interpreter; the kernel bodies are identical on TPU, where they compile —
see test_tpu_compile.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fixedpoint import dequantize, quantize
from repro.kernels.flash_attention import flash_attention
from repro.kernels.packet_accum import packet_accumulate
from repro.kernels.ref import (dequantize_ref, flash_attention_ref,
                               packet_accumulate_ref, quantize_ref)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False


# ------------------------------------------------------------- fixed point
@pytest.mark.parametrize("shape", [(16,), (100,), (257,), (8, 128), (3, 5, 7),
                                   (1024, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_matches_ref(shape, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), shape) * 5).astype(dtype)
    scale = 2.0 ** 16
    got = quantize(x, scale)
    want = quantize_ref(x, scale)
    assert got.dtype == jnp.int32 and got.shape == shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(64,), (300,), (16, 16)])
def test_dequantize_roundtrip(shape):
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    scale = 2.0 ** 20
    d = dequantize(quantize(x, scale), scale)
    np.testing.assert_allclose(np.asarray(d), np.asarray(x), atol=2 / scale)
    np.testing.assert_allclose(np.asarray(d),
                               np.asarray(dequantize_ref(quantize_ref(x, scale), scale)),
                               atol=0)


def test_fixed_point_sum_order_independent():
    """The determinism guarantee behind fixed-point dynamic trees: integer
    partial sums are identical under any association order."""
    xs = [jax.random.normal(jax.random.PRNGKey(i), (256,)) for i in range(8)]
    scale = 2.0 ** 18
    qs = [np.asarray(quantize(x, scale)) for x in xs]
    import itertools, random
    ref_sum = sum(qs)
    rng = random.Random(0)
    for _ in range(5):
        order = list(range(8))
        rng.shuffle(order)
        acc = np.zeros_like(qs[0])
        for i in order:
            acc = acc + qs[i]
        np.testing.assert_array_equal(acc, ref_sum)


# --------------------------------------------------------- packet accumulate
@pytest.mark.parametrize("n,d,slots", [(10, 8, 4), (128, 128, 16),
                                       (1000, 64, 32), (77, 200, 7)])
def test_packet_accumulate_matches_ref(n, d, slots):
    key = jax.random.PRNGKey(2)
    ids = jax.random.randint(key, (n,), 0, slots)
    pay = jax.random.normal(jax.random.PRNGKey(3), (n, d))
    got = packet_accumulate(ids, pay, slots)
    want = packet_accumulate_ref(ids, pay, slots)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,d,slots", [(10, 8, 4), (128, 128, 16),
                                       (1000, 64, 32), (77, 200, 7)])
def test_packet_accumulate_int32_matches_ref(n, d, slots):
    """Fixed-point payloads keep their dtype: int32 in, int32 accumulators
    out, bit-exact against the segment-sum oracle."""
    ids = jax.random.randint(jax.random.PRNGKey(8), (n,), 0, slots)
    pay = jax.random.randint(jax.random.PRNGKey(9), (n, d),
                             -1_000_000, 1_000_000, dtype=jnp.int32)
    got = packet_accumulate(ids, pay, slots)
    want = packet_accumulate_ref(ids, pay, slots)
    assert got.dtype == jnp.int32 and want.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packet_accumulate_int32_wraps_like_int32_addition():
    """Full-range int32 payloads, negative and overflowing: the byte-split
    accumulation must give exactly the wrapped int32 sum."""
    n, d, slots = 300, 130, 3
    ids = jax.random.randint(jax.random.PRNGKey(11), (n,), 0, slots)
    info = jnp.iinfo(jnp.int32)
    pay = jax.random.randint(jax.random.PRNGKey(12), (n, d), info.min,
                             info.max, dtype=jnp.int32)
    got = packet_accumulate(ids, pay, slots)
    want = packet_accumulate_ref(ids, pay, slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_packet_accumulate_drops_out_of_range_ids(dtype):
    """Ids outside [0, num_slots) contribute nothing, as in segment_sum."""
    ids = jnp.array([0, -1, 2, 5, 9, 2, 1], jnp.int32)
    pay = jnp.arange(7 * 4, dtype=dtype).reshape(7, 4)
    got = packet_accumulate(ids, pay, 3)
    want = packet_accumulate_ref(ids, pay, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packet_accumulate_rejects_wrapping_int_dtypes():
    """Non-int32 integer payloads would silently wrap if cast — reject."""
    ids = jnp.zeros(4, jnp.int32)
    pay = jnp.ones((4, 8), jnp.uint32)
    with pytest.raises(TypeError):
        packet_accumulate(ids, pay, 2)
    with pytest.raises(TypeError):
        packet_accumulate_ref(ids, pay, 2)


def test_packet_accumulate_int32_associative():
    """Accumulating the same int32 packets under any slot grouping gives
    totals identical to a direct integer sum (the §6 associativity prize)."""
    pay = jax.random.randint(jax.random.PRNGKey(10), (64, 16),
                             -1_000_000, 1_000_000, dtype=jnp.int32)
    ids_one = jnp.zeros(64, jnp.int32)
    out = packet_accumulate(ids_one, pay, 1)
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(jnp.sum(pay, axis=0)))


def test_packet_accumulate_empty_slots_zero():
    ids = jnp.array([1, 1, 1], jnp.int32)
    pay = jnp.ones((3, 4))
    out = packet_accumulate(ids, pay, 8)
    assert float(out[0].sum()) == 0.0
    np.testing.assert_allclose(np.asarray(out[1]), 3.0)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,H,KV,S,D", [
    (1, 4, 4, 128, 64),      # MHA
    (2, 4, 2, 256, 64),      # GQA 2:1
    (1, 8, 2, 128, 128),     # GQA 4:1
    (1, 2, 1, 512, 64),      # MQA-ish
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, KV, S, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = (jax.random.normal(ks[0], (B, H, S, D)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, KV, S, D)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, KV, S, D)) * 0.5).astype(dtype)
    got = flash_attention(q, k, v, causal=True, bq=128, bk=128)
    want = flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64)) * 0.5
    k = jax.random.normal(ks[1], (1, 2, 128, 64)) * 0.5
    v = jax.random.normal(ks[2], (1, 2, 128, 64)) * 0.5
    got = flash_attention(q, k, v, causal=False)
    want = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_chunked_path():
    """Cross-check the Pallas kernel against the model's jnp chunked
    attention (two independent implementations of the same math)."""
    from repro.models.layers import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    B, H, KV, S, D = 1, 4, 2, 256, 64
    q = jax.random.normal(ks[0], (B, S, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, S, KV, D)) * 0.5
    v = jax.random.normal(ks[2], (B, S, KV, D)) * 0.5
    got_model = chunked_attention(q, k, v, causal=True, chunk=128)
    got_kernel = flash_attention(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(got_model),
                               np.asarray(got_kernel.transpose(0, 2, 1, 3)),
                               rtol=2e-4, atol=2e-4)


if HAVE_HYP:
    @given(st.integers(1, 300), st.integers(1, 64), st.integers(1, 16))
    @settings(max_examples=25, deadline=None)
    def test_packet_accumulate_property(n, d, slots):
        ids = jax.random.randint(jax.random.PRNGKey(n), (n,), 0, slots)
        pay = jax.random.normal(jax.random.PRNGKey(n + 1), (n, d))
        got = packet_accumulate(ids, pay, slots)
        want = packet_accumulate_ref(ids, pay, slots)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
