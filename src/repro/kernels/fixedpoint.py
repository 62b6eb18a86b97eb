"""Pallas TPU kernels: fixed-point quantize / dequantize.

The paper (§6) notes programmable switches have no FPUs, so in-network
allreduce payloads are converted to fixed point before hitting the fabric.
On TPU we keep the same trick for a different prize: integer accumulation is
associative, so a Canary-style *dynamic* tree produces bit-identical sums no
matter which tree shape each block took.

VMEM tiling: elementwise over (8k, 128)-aligned tiles; the scalar scale rides
in SMEM. Kernels are validated in interpret mode against ``ref.py`` and
compile for the TPU (``tests/kernels/test_tpu_compile.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mode

TILE_ROWS = 256
TILE_COLS = 128


def _quant_kernel(scale_ref, x_ref, o_ref):
    o_ref[...] = jnp.round(
        x_ref[...].astype(jnp.float32) * scale_ref[0]).astype(jnp.int32)


def _dequant_kernel(scale_ref, q_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) / scale_ref[0]


def quantize(x: jnp.ndarray, scale) -> jnp.ndarray:
    """Elementwise fixed-point quantization via a tiled Pallas kernel."""
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = TILE_COLS
    rows = max(1, -(-n // cols))
    grid_rows = -(-rows // TILE_ROWS)
    padded_rows = grid_rows * TILE_ROWS
    pad = padded_rows * cols - n
    x2 = jnp.pad(flat, (0, pad)).reshape(padded_rows, cols)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1)
    out = pl.pallas_call(
        _quant_kernel,
        grid=(grid_rows,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE_ROWS, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_rows, cols), jnp.int32),
        interpret=mode.interpret(),
    )(scale_arr, x2)
    return out.reshape(-1)[:n].reshape(orig_shape)


def dequantize(q: jnp.ndarray, scale) -> jnp.ndarray:
    orig_shape = q.shape
    flat = q.reshape(-1)
    n = flat.shape[0]
    cols = TILE_COLS
    rows = max(1, -(-n // cols))
    grid_rows = -(-rows // TILE_ROWS)
    padded_rows = grid_rows * TILE_ROWS
    pad = padded_rows * cols - n
    q2 = jnp.pad(flat, (0, pad)).reshape(padded_rows, cols)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(grid_rows,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE_ROWS, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_ROWS, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_rows, cols), jnp.float32),
        interpret=mode.interpret(),
    )(scale_arr, q2)
    return out.reshape(-1)[:n].reshape(orig_shape)
