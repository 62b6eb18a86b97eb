"""Public allreduce API: Canary-style gradient synchronization for pytrees.

``canary_allreduce_tree``: reduce a whole gradient pytree along the data
axes, Canary-style — the tree is flattened into blocks, each block rides its
own reduction tree (root chosen by the congestion oracle), and multi-axis
meshes reduce hierarchically (pod-local trees, then cross-pod exchange).

Optional fixed-point mode quantizes blocks to int32 before reduction
(paper §6: switch ALUs are integer-only). Integer addition is associative,
so the result is bit-identical no matter which dynamic tree shape the blocks
took — a beyond-paper determinism guarantee.
"""
from __future__ import annotations

from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .congestion import CongestionOracle, round_robin_roots
from .trees import (hierarchical_allreduce, multi_root_tree_allreduce,
                    ring_allreduce, tree_reduce_broadcast)

DEFAULT_BLOCKS = 16


def _psum_safe(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """psum with the XLA:CPU bf16 AllReducePromotion crash workaround
    (see trees._rs_dtype); native bf16 on TPU."""
    from .trees import _rs_dtype
    return lax.psum(_rs_dtype(x), axis).astype(x.dtype)


def _leaf_allreduce(x, axis_name: str, axis_size: int, roots: Sequence[int],
                    mode: str, outer_axis: Optional[str]) -> jnp.ndarray:
    if mode == "canary":
        y = multi_root_tree_allreduce(x, axis_name, axis_size, roots)
        if outer_axis is not None:
            y = _psum_safe(y, outer_axis)
        return y
    if mode == "ring":
        y = ring_allreduce(x, axis_name)
        if outer_axis is not None:
            y = _psum_safe(y, outer_axis)
        return y
    if mode == "hierarchical":
        if outer_axis is None:
            return ring_allreduce(x, axis_name)
        return hierarchical_allreduce(x, axis_name, outer_axis)
    if mode == "psum":
        y = _psum_safe(x, axis_name)
        if outer_axis is not None:
            y = _psum_safe(y, outer_axis)
        return y
    raise ValueError(f"unknown grad-sync mode {mode}")


def canary_allreduce_tree(grads: Any, *, axis_name: str, axis_size: int,
                          roots: Optional[Sequence[int]] = None,
                          num_blocks: int = DEFAULT_BLOCKS,
                          mode: str = "canary",
                          outer_axis: Optional[str] = None,
                          fixed_point: bool = False,
                          fp_bits: int = 24,
                          leaf_specs: Any = None) -> Any:
    """Allreduce every leaf of ``grads`` along ``axis_name`` (+``outer_axis``).

    mode: canary (multi-root trees) | ring (RS+AG) | hierarchical | psum.
    ``leaf_specs``: a tree of PartitionSpecs matching ``grads``, how each
    leaf is split over the mesh axes that are still automatic; the
    fixed-point path reduces each device's part on its own. ``None``:
    every leaf is replicated over those axes.
    """
    if roots is None:
        roots = round_robin_roots(num_blocks, axis_size)
    if leaf_specs is None:
        leaf_specs = jax.tree.map(lambda _: P(), grads)

    def one(x, spec):
        if fixed_point and mode == "canary":
            from repro.kernels.ops import fixed_point_allreduce_wrap
            gmax = lax.pmax(jnp.max(jnp.abs(x.astype(jnp.float32))), axis_name)
            world = axis_size
            if outer_axis is not None:
                gmax = lax.pmax(gmax, outer_axis)
                world *= lax.axis_size(outer_axis)
            return fixed_point_allreduce_wrap(
                x, lambda q: _leaf_allreduce(q, axis_name, axis_size, roots,
                                             mode, outer_axis),
                gmax, bits=fp_bits, world=world, spec=spec)
        return _leaf_allreduce(x, axis_name, axis_size, roots, mode,
                               outer_axis)

    return jax.tree.map(one, grads, leaf_specs)
