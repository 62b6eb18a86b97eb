"""jit'd public wrappers for the Pallas kernels.

Every kernel compiles on a TPU and runs in the Pallas interpreter elsewhere
(:func:`repro.kernels.mode.interpret`).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from .fixedpoint import dequantize, quantize
from .flash_attention import flash_attention
from .packet_accum import packet_accumulate

quantize_op = jax.jit(quantize)
dequantize_op = jax.jit(dequantize)
packet_accumulate_op = jax.jit(packet_accumulate,
                               static_argnames=("num_slots",))
flash_attention_op = jax.jit(flash_attention, static_argnames=("causal",))


def split_over_auto_axes(kernel: Callable, x: jnp.ndarray, scale,
                         spec: P = P()) -> jnp.ndarray:
    """``kernel(x, scale)`` with the compiler never asked to partition it.

    Mosaic kernels cannot be partitioned automatically. Where the enclosing
    mesh still has automatic axes (the grad-sync ``shard_map`` is manual over
    the data axes only), the call gets a ``shard_map`` of its own, manual
    over those axes, with ``x`` split as ``spec`` says: elementwise kernels
    then run on each device's own part of ``x``.
    """
    mesh = jax.sharding.get_abstract_mesh()
    auto = {name for name, kind in zip(mesh.axis_names, mesh.axis_types)
            if kind != AxisType.Manual}
    if not auto:
        return kernel(x, scale)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, P()),
                         out_specs=spec, axis_names=auto,
                         check_vma=False)(x, scale)


def fixed_point_scale(gmax, *, bits: int, world: int):
    """Shared quantization scale for fixed-point reduction paths: ``gmax``
    is the global max |x| across participants (every device must use the
    same scale); headroom for ``world`` summands prevents int32 overflow."""
    return (2.0 ** bits - 1.0) / (gmax * world + 1e-30)


def fixed_point_allreduce_wrap(x: jnp.ndarray,
                               reduce_fn: Callable[[jnp.ndarray], jnp.ndarray],
                               gmax: jnp.ndarray, bits: int, world: int,
                               spec: P = P()) -> jnp.ndarray:
    """Quantize -> integer reduce -> dequantize (paper §6 switch arithmetic).

    Integer addition is associative, so the result is bit-identical for any
    dynamic tree shape. ``spec``: how ``x`` is split over the mesh axes that
    are still automatic (see :func:`split_over_auto_axes`).
    """
    scale = fixed_point_scale(gmax, bits=bits, world=world)
    q = split_over_auto_axes(quantize, x, scale, spec)
    r = reduce_fn(q)
    return split_over_auto_axes(dequantize, r, scale, spec).astype(x.dtype)
