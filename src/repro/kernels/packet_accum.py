"""Pallas TPU kernel: packet -> descriptor accumulation (switch aggregation).

The hot loop of the paper's data plane (§3.1.1): every arriving packet's
payload is summed into the descriptor slot its block id hashes to. As a
TPU kernel this is a segment-sum; the TPU-native formulation is a one-hot
matmul per packet tile — the MXU performs the scatter-accumulate, and each
(slots, PAY_TILE) accumulator block is revisited across the packet tiles of
the grid (a standard Pallas accumulation pattern).

Accumulation dtype follows the payload: int32 payloads accumulate (and
return) int32 — the associative fixed-point path (§6: switch ALUs are
integer-only) that makes dynamic-tree replay bit-deterministic — while float
payloads accumulate in float32. The MXU of a v5e has no int32 matmul, so
int32 payloads are split into their four bytes: each byte is exact in
bfloat16, a tile's byte sums (at most PKT_TILE * 255) are exact in the
float32 accumulator, and the bytes recombine with wrapping int32 shifts —
the same sum modulo 2**32 that int32 addition gives.

Used by the software switch emulation benchmarks (Fig. 6), the trace-replay
executor (``repro.core.trace.executor``) and validated against
``ref.packet_accumulate_ref`` over shape/dtype sweeps.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mode

PKT_TILE = 128   # packets per grid step (the one-hot contraction width)
PAY_TILE = 128   # payload lanes per output block
SLOT_ALIGN = 8   # slots pad to whole sublanes


def _int32_onehot_sum(onehot: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``onehot (S, K) @ x (K, D)`` for int32 ``x``, exact modulo 2**32,
    with bfloat16 matmuls only (see the module docstring)."""
    oh = onehot.astype(jnp.bfloat16)
    total = None
    for k in range(4):
        byte = ((x >> (8 * k)) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        part = jnp.dot(oh, byte, preferred_element_type=jnp.float32)
        part = part.astype(jnp.int32) << (8 * k)
        total = part if total is None else total + part
    return total


def _accum_kernel(ids_ref, x_ref, o_ref, *, acc_dtype):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ids = ids_ref[0]                                     # (1, PKT_TILE)
    onehot = jax.lax.broadcasted_iota(
        jnp.int32, (o_ref.shape[0], ids.shape[1]), 0) == ids   # (slots, pkts)
    if acc_dtype == jnp.int32:
        o_ref[...] += _int32_onehot_sum(onehot, x_ref[...])
    else:
        o_ref[...] += jnp.dot(onehot.astype(jnp.float32),
                              x_ref[...].astype(jnp.float32),
                              preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)


def accumulate_dtype(payload_dtype) -> jnp.dtype:
    """int32 payloads accumulate in int32 (associative); floats in float32.

    Other integer dtypes are rejected: casting them to int32 would silently
    wrap (int64/uint32) and the fixed-point contract is int32-exact.
    """
    if jnp.issubdtype(payload_dtype, jnp.integer):
        if jnp.dtype(payload_dtype) != jnp.dtype(jnp.int32):
            raise TypeError(f"integer payloads must be int32 (got "
                            f"{jnp.dtype(payload_dtype).name}); quantize via "
                            f"repro.kernels.fixedpoint first")
        return jnp.int32
    return jnp.float32


def packet_accumulate(slot_ids: jnp.ndarray, payloads: jnp.ndarray,
                      num_slots: int) -> jnp.ndarray:
    """slot_ids: (N,) int32; payloads: (N, D) -> (num_slots, D).

    Output dtype is :func:`accumulate_dtype` of the payload dtype: int32 for
    integer payloads, float32 otherwise. Ids outside ``[0, num_slots)`` are
    dropped, as ``jax.ops.segment_sum`` drops them.
    """
    n, d = payloads.shape
    acc_dtype = accumulate_dtype(payloads.dtype)
    n_tiles = -(-n // PKT_TILE)
    d_tiles = -(-d // PAY_TILE)
    slots = -(-num_slots // SLOT_ALIGN) * SLOT_ALIGN
    pad_n = n_tiles * PKT_TILE - n
    # padded packets carry id -1, which matches no slot; ids in
    # [num_slots, slots) land in padding rows that are sliced away
    ids = jnp.pad(slot_ids.astype(jnp.int32), (0, pad_n), constant_values=-1)
    ids = ids.reshape(n_tiles, 1, PKT_TILE)
    pay = jnp.pad(payloads, ((0, pad_n), (0, d_tiles * PAY_TILE - d)))
    out = pl.pallas_call(
        partial(_accum_kernel, acc_dtype=acc_dtype),
        grid=(d_tiles, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, PKT_TILE), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((PKT_TILE, PAY_TILE), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((slots, PAY_TILE), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((slots, d_tiles * PAY_TILE),
                                       acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=mode.interpret(),
    )(ids, pay)
    return out[:num_slots, :d]
