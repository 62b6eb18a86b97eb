"""JAX executor: replay a compiled schedule as a real tensor program.

Each reduce round of a :class:`~repro.core.trace.schedule.Schedule` is one
segment-sum — every step's source buffers are stacked into a packet matrix
and scatter-accumulated into per-destination slots by
:func:`repro.kernels.packet_accum.packet_accumulate` (the MXU one-hot-matmul
kernel the software-switch benchmarks use, through its jitted wrapper so
that each round shape compiles once), exactly the per-switch
aggregation of §3.1.1. The broadcast phase replicates the root buffer down
the mirrored tree (§3.1.2).

Two numeric modes:

* **float32** — matches a plain ``sum(inputs)`` up to re-association error
  (the tree decides the association order, so different recorded trees give
  slightly different floats — the non-determinism the paper inherits from
  floating point).
* **int32 fixed point** — inputs are quantized via
  :mod:`repro.kernels.fixedpoint` and accumulated as int32. Integer addition
  is associative, so the result is **bit-identical for every tree shape the
  timeouts produced** — the beyond-paper determinism claim, demonstrated on
  trees the simulator actually formed under congestion.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from repro.kernels.ops import (dequantize_op, fixed_point_scale,
                               packet_accumulate_op, quantize_op)
from repro.kernels.packet_accum import accumulate_dtype

from .schedule import Schedule


def replay_block(schedule: Schedule, inputs: jnp.ndarray) -> jnp.ndarray:
    """Replay one block's schedule over per-host input rows.

    ``inputs``: ``(P, D)`` — row ``r`` is the contribution of
    ``schedule.hosts[r]``. Returns ``(P, D)``: every host's post-broadcast
    buffer (all rows identical — the reduced block). int32 inputs are
    accumulated in int32 (associative), floats in float32.
    """
    hosts = schedule.hosts
    if inputs.shape[0] != len(hosts):
        raise ValueError(f"inputs has {inputs.shape[0]} rows for "
                         f"{len(hosts)} participants")
    rank = {h: r for r, h in enumerate(hosts)}
    inputs = inputs.astype(accumulate_dtype(inputs.dtype))

    buffers = {}
    for nid, host in schedule.leaf_host.items():
        buffers[nid] = inputs[rank[host]]

    for rnd in schedule.reduce_rounds:
        slot_ids = []
        payloads = []
        for slot, step in enumerate(rnd):
            for src in step.srcs:
                slot_ids.append(slot)
                payloads.append(buffers[src])
        acc = packet_accumulate_op(jnp.asarray(slot_ids, jnp.int32),
                                   jnp.stack(payloads), num_slots=len(rnd))
        for slot, step in enumerate(rnd):
            buffers[step.dst] = acc[slot]

    # broadcast: every step of the mirrored tree is a copy of the root
    # buffer, so the per-host rows materialize directly
    total = buffers[schedule.root]
    return jnp.broadcast_to(total, (len(hosts),) + total.shape)


def replay_app(schedules: Sequence[Schedule], inputs: jnp.ndarray
               ) -> jnp.ndarray:
    """Replay a whole app: ``inputs`` is ``(P, B, D)`` (one row of blocks per
    participant, in ``schedules[b].hosts`` order); returns ``(P, B, D)``."""
    if inputs.shape[1] != len(schedules):
        raise ValueError(f"inputs has {inputs.shape[1]} blocks for "
                         f"{len(schedules)} schedules")
    outs = [replay_block(s, inputs[:, b]) for b, s in enumerate(schedules)]
    return jnp.stack(outs, axis=1)


def fixed_point_replay(schedules: Sequence[Schedule], x: jnp.ndarray, *,
                       bits: int = 24):
    """Fixed-point replay: quantize -> int32 tree accumulation -> dequantize.

    ``x``: ``(P, B, D)`` float inputs. Returns ``(result, q_result)`` where
    ``q_result`` is the raw ``(P, B, D)`` int32 accumulation — bit-identical
    across any set of recorded tree shapes for the same ``x`` — and
    ``result`` is its dequantized float32 view. The scale is the shared
    :func:`repro.kernels.ops.fixed_point_scale` (same convention as
    ``fixed_point_allreduce_wrap``): a global max with headroom for ``P``
    summands so int32 never overflows.
    """
    gmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = fixed_point_scale(gmax, bits=bits, world=x.shape[0])
    q = quantize_op(x, scale)
    q_result = replay_app(schedules, q)
    return dequantize_op(q_result, scale), q_result


def reference_allreduce(x: jnp.ndarray) -> jnp.ndarray:
    """The float oracle: every participant receives ``sum_r x[r]``."""
    total = jnp.sum(x.astype(jnp.float32), axis=0)
    return jnp.broadcast_to(total, x.shape)
